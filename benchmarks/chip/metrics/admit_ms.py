"""Mean admission time per segment in the window
(``StreamExecutor.last_segment_stats[*].admit_s``)."""


def read(run):
    segs = run.window_segments()
    return 1e3 * sum(s["admit_s"] for s in segs) / len(segs) if segs else None
