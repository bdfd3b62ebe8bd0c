"""Distributed tracing: trace/span propagation across every cross-node
hop, a bounded span ring buffer, and the /debug/traces payload.

Public surface: start_span / add_event for instrumentation, inject /
extract / injectable for transports, BUFFER + debug_traces_payload for
the status servers, configure for tests and drills, StageAccount
(stages.py) for the stage sums of one long operation.
"""

from .stages import RPC_ACCOUNT, StageAccount

from .trace import (
    BUFFER, Span, SpanContext, TRACEPARENT_HEADER, TraceBuffer, add_event,
    configure, current_ids, current_span, current_trace_id,
    debug_traces_payload, extract, inject, injectable, parse_traceparent,
    sample_rate, start_span,
)

__all__ = [
    "BUFFER", "RPC_ACCOUNT", "Span", "SpanContext", "StageAccount",
    "TRACEPARENT_HEADER", "TraceBuffer",
    "add_event", "configure", "current_ids", "current_span",
    "current_trace_id", "debug_traces_payload", "extract", "inject",
    "injectable", "parse_traceparent", "sample_rate", "start_span",
]
