"""Trajectories waiting in the learner's queue, time-weighted over the
window: the depth integrated between the window's edges over its
length (``distributed/tqueue.py`` keeps the integral). A full queue
means the learner is the bottleneck; an empty one, the actors."""


def compute(ctx):
    a0, a1 = ctx.start["queue_area"], ctx.end["queue_area"]
    if a0 is None or a1 is None:
        return None
    return (a1 - a0) / ctx.window_s
