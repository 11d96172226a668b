"""Mean bytes of engine state that ``StreamExecutor.run`` copied per
segment in the window (the ``copy_bytes`` counter of each segment's
``last_segment_stats`` entry), in GiB."""


def read(run):
    segs = [s for s in run.window_segments() if "counts" in s]
    if not segs:
        return None
    return sum(s["counts"].get("copy_bytes", 0)
               for s in segs) / len(segs) / 2 ** 30
