"""In-scan telemetry plane: zero-dispatch metrics buffers + run ledger.

Device side (``spec``): a ``MetricSpec`` registry the engine builders
declare probes into; stages emit frames that ride the scan supersteps as
stacked ys — no extra dispatches, and ``telemetry=None`` traces nothing
(bit-identical to the golden engine path).

Host side (``ledger``/``sinks``): ``RunLedger`` unifies the drivers'
dispatch/wall-clock accounting and flushes probe frames at eval
boundaries into a JSONL sink with a run manifest header.

Host spans (``trace``): ``defta:`` profiler annotations where the host
works, compile markers, and the map from a program's HLO instructions to
its pipeline stages.
"""
from repro.telemetry.spec import (
    MetricSpec,
    Telemetry,
    cross_device_specs,
    defta_specs,
    fedavg_specs,
    frame_bytes,
    stacked_payload_bytes,
    tick_specs,
    tree_payload_bytes,
    wire_payload_bytes,
)
from repro.telemetry.ledger import RunLedger
from repro.telemetry.sinks import JsonlSink, git_digest, run_manifest

__all__ = [
    "MetricSpec",
    "Telemetry",
    "RunLedger",
    "JsonlSink",
    "git_digest",
    "run_manifest",
    "frame_bytes",
    "wire_payload_bytes",
    "stacked_payload_bytes",
    "tree_payload_bytes",
    "defta_specs",
    "tick_specs",
    "fedavg_specs",
    "cross_device_specs",
]
