"""Host time per interval between windows spent resetting the slots that
joins and leaves marked (`fleet.slot_reset` spans: one dispatch of
`jit_slot_reset` for every pending slot, not waited on).  None where the
program opens no such span."""
from bench import stages


def read(ctx):
    tr = stages.tracer()
    if tr is None or not any(s["name"] == "fleet.slot_reset"
                             for s in tr.spans):
        return None
    return stages.host_ms(ctx, "fleet.slot_reset")
