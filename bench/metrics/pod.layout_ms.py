"""Device time of the ops under the `pod_round.*` named scopes
(`dist.steps.make_pod_round_step`: the unflatten of the blocked global
parameters, and the flatten, pad and reshape of the pods' deltas with the
mean of their losses), per chip and round of the traced window.

The compiler may merge an op of this layer with one of another into one
instruction, whose op name then joins both (`a;b`). Such an op stays with
`pod.local_round_ms` or `pod.sync_ms`, which count it already, so that
the four layer metrics tile the device's busy time."""
import re

SCOPES = ("pod_round.unflatten", "pod_round.flatten")
OWN = re.compile(r"(^|[/(])(" + "|".join(map(re.escape, SCOPES))
                 + r")([/)]|$)")
OTHER = re.compile(r"(^|[/(])(local_round|pod_sync\.\w+)([/)]|$)")


def counts(scope: str) -> bool:
    """Whether an op with this op name is this layer's."""
    return bool(OWN.search(scope)) and not OTHER.search(scope)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(e.end - e.start for evs in ctx.trace.devices.values()
            for e in evs if counts(e.scope))
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
