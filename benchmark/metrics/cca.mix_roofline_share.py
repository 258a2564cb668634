"""The least time the chip could take for the mixing of every block of
compressed convolutional attention of one step, **as written** (a token
and layer: the grouped convolution's ``2 x taps x heads x D x D`` FLOPs,
and as bytes ``[qt | kt]`` read once and ``qh``, ``kh`` written once at 2
B; 3x forward: ``lib/counts_cca.py``) over the device time of
``moolib.lm.cca_mix``. The same work whatever implements the scope; over
100% means the count is wrong. None where the program has no such scope
or its description no kind with ``cca``."""
from benchmark.lib import counts_cca, readers_cca, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, counts_cca.MIX_SCOPE
    )
    parts = readers_cca.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    r = counts_cca.mix_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"], readers_lm.chip_peaks(context),
    )
    print(f"[roofline] the compressed attention's mixing of one step, as "
          f"written: {r['flops']:.4g} FLOPs, {r['least_bytes']:.4g} bytes, "
          f"{r['least_seconds'] * 1e3:.3f} ms at peak (bound by "
          f"{r['bound_by']}); device time {seconds * 1e3:.3f} ms", flush=True)
    return 100.0 * r["least_seconds"] / seconds
