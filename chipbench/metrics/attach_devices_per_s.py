"""Late devices attached per second while draining a backlog: every
device whose labels reached the caller in the window, over the whole
window (host clock)."""
SOURCE = "host_clock"


def read(rec):
    done = len(rec.delivered())
    return done / rec.window_s if done and rec.window_s > 0 else None
