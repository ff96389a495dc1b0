"""Seconds per job: from the window's start to the end of the last job
completed before the deadline, divided by the jobs completed by then."""


def read(run):
    s = run.stream("jobs")
    if s is None:
        return None
    done = sorted(t for t, _ in s.done if t <= run.window.deadline)
    if not done:
        return None
    return (done[-1] - run.window.t0) / len(done)
