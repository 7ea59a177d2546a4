"""Share of the window's live lanes that ran the node-aware recurrence:
the program counter ``fastsim.lanes_node_aware`` over ``fastsim.lanes_live``.
1.0 where every request of a machine with several ranks a node is priced
by its node; lower where traffic bypasses it.  Nothing is read where no
lane ran, or where the program has no node-aware recurrence."""


def read(run):
    from repro.core import fastsim
    live = run.window_stats.get("fastsim.lanes_live", 0)
    if not live or not hasattr(fastsim, "node_block"):
        return None
    return run.window_stats.get("fastsim.lanes_node_aware", 0) / live
