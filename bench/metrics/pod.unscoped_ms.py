"""Device time of the ops under none of the pod round's layer scopes
(`local_round`, `pod_sync.*`, `pod_round.*`), per chip and round of the
traced window: what the layer map cannot see. Ops the compiler inserts
with no op metadata (layout copies, relayout loops) count here.
`pod.local_round_ms + pod.sync_ms + pod.layout_ms + pod.unscoped_ms` is
the device's busy time per round."""
import re

LAYER = re.compile(r"(^|[/(])(local_round|pod_sync\.\w+|pod_round\.\w+)"
                   r"([/)]|$)")


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    s = sum(e.end - e.start for evs in ctx.trace.devices.values()
            for e in evs if not LAYER.search(e.scope))
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
