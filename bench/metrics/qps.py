"""Queries answered per second: every request offered in the window,
over the time from the window's opening to the last of their answers.

Above capacity the queue grows all window, so a count of answers inside
the window depends on where the close falls within a service time; the
whole offered work over the whole time it took is steadier and leaves
nothing unfinished uncounted.  An unanswered request fails the run's
correctness check, so the rate is over all of them."""


def read(ctx):
    done = [r.done for r in ctx.requests if r.done is not None]
    return len(done) / max(done) if done else None
