"""Requests per second: every request of the window over the time from the
window's start to the last completion (requests that started before the
deadline are finished, so all the work and all its time are in)."""


def read(m):
    win = m["window"]
    done = len(win.completed)
    return done / (win.end - win.t0) if done else None
