"""Which side sets the pace, with no profiler listening: of the steps
dispatched while the step before them was uncollected, the share whose
result was NOT yet there when the host came back to read it (the device was
still running: it paced the step; ready: the host did). 100 x (1 -
``ready_share``) of the ``untraced`` segment of ``step_account``; None where
the program keeps no account or no such step said."""

from benchmark import stepaccount


def read(obs):
    seg = stepaccount.segment(obs)
    if seg is None or seg.get("ready_share") is None:
        return None
    return 100.0 * (1.0 - seg["ready_share"])
