"""Device time of the ops charged to the trainer's ``mll.grads`` scope
(forward, loss with the tied head, backward; gradient ops that XLA fuses
into the update or the mixing count there) per slot whose step program
ran the model, averaged over the cell's chips."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    return spans.per_slot_ms(
        spans.part_ns(m, spans.GRADS), spans.model_slots(m))
