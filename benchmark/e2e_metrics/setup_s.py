"""Process start to the first timed operation: imports, plan, compile or
cache load, state on the device, warm-up. The reference check runs after the
window and is not in it."""


def read(m):
    return m["setup_s"]
