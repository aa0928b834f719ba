"""What the readers of the serving loop's step account share.

``/stats`` of a generative server holds ``step_account``: by segment
(``untraced``: before any profiler session was seen in the process;
``traced``; ``untraced_after_trace``) the loop's period a collected step and
the exclusive time of every span that closed on the loop thread inside it,
as ``{p50, p90}`` in ms, written by the program with no profiler listening.
The readers take the ``untraced`` segment: the server as its users meet it.
A program that keeps no account (every commit before PR 39), or a segment
with fewer than ``MIN_STEPS`` rows, reads None.
"""

MIN_STEPS = 200

#: the per-layer metrics whose readers go through this file (PR 39), all of
#: them listed for the four serving cells
READERS = (
    "kv.step_period_ms.untraced", "kv.step_host_ms.untraced",
    "kv.step_prepare_ms.untraced", "kv.step_upload_ms.untraced",
    "kv.step_launch_ms.untraced", "kv.step_fetch_wait_ms.untraced",
    "sched.retire_ms.untraced", "sched.gauges_ms.untraced",
    "sched.step_unattributed_share", "kv.device_paced_step_share",
)


def segment(obs, name="untraced"):
    """The account's segment ``name`` of an observation, or None."""
    stats = (obs.get("serve") or {}).get("executor_stats") or {}
    seg = (stats.get("step_account") or {}).get(name)
    if not seg or seg.get("rows", 0) < MIN_STEPS:
        return None
    return seg


def p50_ms(obs, key, phase=None):
    """The median of ``key`` (``period_ms``, ``host_ms``, ``other_ms``) or,
    with ``key="phases_ms"``, of the span ``phase``; None where absent."""
    seg = segment(obs)
    if seg is None:
        return None
    entry = seg.get(key) or {}
    if phase is not None:
        entry = entry.get(phase) or {}
    return entry.get("p50")
