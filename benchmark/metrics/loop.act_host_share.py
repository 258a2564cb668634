"""Share of the loop's wall time in the host's side of acting, the
blocking readback (``loop.host_sync_share``) and the wait for the envs
(``loop.env_wait_share``) apart: ``unroll_cat``, ``obs_stage``,
``act_dispatch`` and ``env_submit``."""
from benchmark.lib.spans import share_of_wall


def read(readings, context):
    return share_of_wall(
        readings, ("unroll_cat", "obs_stage", "act_dispatch", "env_submit")
    )
