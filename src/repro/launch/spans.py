"""Names the trainer gives its work in a profiler trace.

Host spans mark what `TrainHarness.run_span` does between launches;
device scopes (`jax.named_scope` with the names below) mark which part of
a slot's program an op belongs to, through the ``op_name`` metadata the
compiled ops carry.  Both land in the trace that
`jax.profiler.start_trace` records, on the profiler's clock.  The profiler
keeps the spans in memory and writes them at `jax.profiler.stop_trace`;
while no trace is active a span costs one flag check.

Span stats (``span(name, slots=k)``) are recorded on the span, beside its
bare name: the slots a span covers are counted where the work happens.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

# host spans of TrainHarness.run_span
RUN_SPAN = "run_span"            # one call; stats lo, hi
DRAW_BATCH = "draw_batch"        # one batch from the data feed
STACK_BATCHES = "stack_batches"  # stack + host->device copy of a chunk
LOCAL_SCAN = "local_scan"        # dispatch of a local scan; stat slots = k
EVENT_STEP = "event_step"        # dispatch of an event slot: .<phase>
DENSE_STEP = "dense_step"        # dispatch of a dense-operator event slot
SKIP_IDLE = "skip_idle"          # all-idle fast-forward; stat slots

# device scopes of train_step.mll_harness_step
GRADS = "mll.grads"              # forward, loss, backward
UPDATE = "mll.update"            # gate draw + gated inner update
MIX_SUBNET = "mll.mix.subnet"    # the mixing branch, by event
MIX_HUB = "mll.mix.hub"
MIX_DENSE = "mll.mix.dense"


def event_step(phase: int) -> str:
    """The dispatch span of an event slot of ``phase`` (1 subnet, 2 hub)."""
    return f"{EVENT_STEP}.{int(phase)}"


# ``with span(name, **stats):`` marks a host span named ``name`` that
# carries ``stats``
span = TraceAnnotation
