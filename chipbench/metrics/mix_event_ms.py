"""Device time of the ops charged to the trainer's ``mll.mix.*`` scopes
per event slot, averaged over the cell's chips: the subnet, hub or dense
mixing with the update and gradient ops that XLA fuses into it (a fusion
counts for the latest part of the step it holds)."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    return spans.per_slot_ms(
        spans.part_ns(m, spans.MIX), spans.event_slots(m))
