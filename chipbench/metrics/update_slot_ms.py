"""Device time of the ops charged to the trainer's ``mll.update`` scope
(the gate draw and the gated inner-optimizer update) in the local scans,
the programs that mix nothing, per local slot, averaged over the cell's
chips.  In an event program XLA fuses most of the update into the
mixing, which `mix_event_ms` reads."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    local = spans.programs(m, spans.LOCAL_SCAN)
    return spans.per_slot_ms(spans.part_ns(m, spans.UPDATE, local),
                             spans.local_slots(m))
