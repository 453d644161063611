"""Hashes per second per chip that the served path delivered.

Useful hashes of every job submitted in the window, counted from the
answers (the range up to and including the winner, or all of it), over
the time from the window's start to the last answer, over the chips.
"""


def read(run):
    done = [r for r in run.records if r["answer"] is not None]
    if not done:
        return None
    elapsed = max(r["t_done"] for r in done) - done[0]["t_start"]
    return sum(r["useful"] for r in done) / elapsed / run.chips
