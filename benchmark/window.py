"""Window arithmetic: what the end-to-end metrics are, in one place."""

from __future__ import annotations


def run_back_to_back(run_job, seconds: float, clock) -> tuple[float, float, list]:
    """Jobs back to back from one client. A job starts only while less than
    ``seconds`` of the window have passed; the window closes when the last
    job started completes. Returns (window start, window end, jobs), each
    job ``(start, end, result)``: all of the window's time and all of its
    jobs count."""
    t0 = clock()
    jobs = []
    while True:
        s = clock()
        if jobs and s - t0 >= seconds:
            break
        res = run_job(len(jobs))
        jobs.append((s, clock(), res))
    return t0, jobs[-1][1], jobs


def train_job_s(t0: float, t1: float, njobs: int) -> float:
    """Seconds of the whole window per training job completed in it."""
    if njobs <= 0:
        raise ValueError("no job completed in the window")
    return (t1 - t0) / njobs
