"""Inserts plus deletes in generations that became visible inside the
window, over the window's length."""


def read(run):
    return sum(b.tuples for b in run.window_batches()) / run.seconds
