"""Mean ``StreamExecutor`` dispatch time per segment in the window
(``last_segment_stats[*].dispatch_s``, the wall of the program's
``fivm.dispatch`` span): the input-state copy and the stream program's
dispatch."""


def read(run):
    segs = run.window_segments()
    return (1e3 * sum(s["dispatch_s"] for s in segs) / len(segs)
            if segs else None)
