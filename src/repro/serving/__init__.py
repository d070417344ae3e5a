"""Serving runtime: samplers, request scheduling, batched speculative server."""
from repro.serving.telemetry import MetricsRegistry, StatsView, span
from repro.serving.exporters import JsonlSink, MetricsHTTPServer
from repro.serving.draft_bank import DraftBank, DraftLevel
from repro.serving.sampler import sample_token
from repro.serving.scheduler import Request, RequestScheduler, ServeLoop
from repro.serving.server import BatchedSpecServer

__all__ = [
    "sample_token", "Request", "RequestScheduler", "ServeLoop",
    "BatchedSpecServer", "DraftBank", "DraftLevel",
    "MetricsRegistry", "StatsView", "span",
    "JsonlSink", "MetricsHTTPServer",
]
