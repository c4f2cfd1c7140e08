"""Serving: batched homomorphic analytics + streaming temporal ingest."""
from .analytics import AnalyticsFrontend, AnalyticsRequest, AppendRequest
from .routing import StoreRouter

__all__ = ["AnalyticsFrontend", "AnalyticsRequest", "AppendRequest",
           "StoreRouter"]
