"""Data: synthetic scientific fields + a compressed field store."""
from . import scientific
