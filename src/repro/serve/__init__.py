"""Serving layer: the query front-end.

:mod:`~repro.serve.frontend` holds :class:`QueryServer`, the
high-throughput multi-client query front-end: shape-bucketed
micro-batching over ``execute_many``, semantic request deduplication, a
version-keyed result cache invalidated by streaming version bumps,
bounded-queue admission control, and planner-calibration feedback.
"""
from .frontend import Overloaded, QueryServer, shape_bucket

__all__ = ["Overloaded", "QueryServer", "shape_bucket"]
