"""Mean ``SnapshotRegistry.publish`` time per segment in the window
(``last_segment_stats[*].publish_s``)."""


def read(run):
    segs = run.window_segments()
    return (1e3 * sum(s["publish_s"] for s in segs) / len(segs)
            if segs else None)
