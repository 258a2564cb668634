"""The least time the chip could take for the recurrence of every
delta-rule block of one step, **as written** (a position and head: the
decay, ``S^T k``, the rank-one update and the read, ``7 D^2`` FLOPs, 3x
forward; or its least bytes; over the peaks: ``lib/counts_kda.py``) over
the device time of ``moolib.lm.kda_core``. The same work whatever
implements the scope; over 100% means the count is wrong. None where the
program has no such scope or its description no kind with ``delta``."""
from benchmark.lib import counts_kda, readers_kda, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, counts_kda.CORE_SCOPE
    )
    parts = readers_kda.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    r = counts_kda.core_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"], readers_lm.chip_peaks(context),
    )
    print(f"[roofline] the delta rule's recurrence of one step, as "
          f"written: {r['flops']:.4g} FLOPs, {r['least_bytes']:.4g} bytes, "
          f"{r['least_seconds'] * 1e3:.3f} ms at peak (bound by "
          f"{r['bound_by']}); device time {seconds * 1e3:.3f} ms", flush=True)
    return 100.0 * r["least_seconds"] / seconds
