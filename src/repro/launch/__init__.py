"""Entry-point support: compile cache, analytics mesh, HLO/jaxpr byte counts."""
