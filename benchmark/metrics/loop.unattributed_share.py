"""Share of the loop's wall time no phase claims (StepScope's ``other``).
Read only from a program that names the parts of its turn: one that does
not has most of its turn in ``other``, which says nothing."""
from benchmark.lib.spans import phase_seconds, share_of_wall


def read(readings, context):
    if phase_seconds(readings, ("acc_update", "learn_stage")) is None:
        return None
    return share_of_wall(readings, ("other",))
