"""The host's time to launch the step programs per slot: the summed
length of the trainer's dispatch spans (``local_scan``, ``event_step.*``,
``dense_step``), each of which covers one call into a compiled program.
Wall time on the host's clock, any wait the runtime imposes on the launch
included: while the launch queue is full it reads back-pressure from the
device, and follows the device's speed."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    return spans.per_slot_ms(
        spans.span_ns(m, spans.DISPATCH), spans.all_slots(m))
