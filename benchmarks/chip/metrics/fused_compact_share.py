"""Share of the window's fused trigger chains that took the compact ⊎
(the ``fused_chains_compact`` over the ``fused_chains`` counter of each
segment's ``last_segment_stats`` entry, summed over the window), as a
fraction. Reads nothing where no segment ran a fused chain."""


def read(run):
    segs = [s for s in run.window_segments() if "counts" in s]
    fused = sum(s["counts"].get("fused_chains", 0) for s in segs)
    if not fused:
        return None
    return sum(s["counts"].get("fused_chains_compact", 0)
               for s in segs) / fused
