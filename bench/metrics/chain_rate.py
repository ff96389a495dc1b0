"""Matvec chains completed per second, over the whole window: from its
start to the end of the last call (calls begun before the deadline)."""


def read(run):
    s = run.stream("chains")
    if s is None or not s.n_chains:
        return None
    return s.n_chains / (s.t_last - run.window.t0)
