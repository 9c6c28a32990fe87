"""The host's time in `TrainHarness.run_span` outside its launches, per
slot: the length of the trainer's ``run_span`` spans less the dispatch
spans inside them (drawing, stacking and copying batches, and the loop's
logic).  Wall time on the host's clock: while the runtime's launch queue
is full, the small programs that stacking launches wait in it too, so
this reads back-pressure from the device as well as the host's work; the
chip's cost of host work is the device's idle time
(`device_idle_share.train`)."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    return spans.per_slot_ms(
        spans.self_ns(m, spans.RUN_SPAN, spans.DISPATCH),
        spans.all_slots(m))
