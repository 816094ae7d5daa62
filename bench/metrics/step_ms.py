"""The whole window over the calls completed in it, in milliseconds."""


def read(r):
    return 1e3 * r.window.seconds / r.window.calls
