"""The measured window over a running job: warm-up, window, end.

All clocks are the host's `time.time()`, the clock the program stamps its
events and log lines with, so spans and samples line up.
"""

import glob
import os
import time

POLL_SECONDS = 0.02
STATUS_FIELDS = (
    "records_done", "relaunches", "tasks_recovered", "tasks_abandoned",
    "todo_tasks", "doing_tasks", "alive_workers", "finished", "job_failed",
)


def status_dict(s):
    out = {k: getattr(s, k) for k in STATUS_FIELDS}
    out["records_done"] = int(out["records_done"])
    return out


def trace_files(profile_dir):
    return glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True
    )


def window_ends(samples, t0, t1):
    """The first and last observed increase of records_done inside
    [t0, t1]: ((t, records), (t, records)) or None with fewer than two.
    A rate between them is a rate between two task completions."""
    inside = [(t, r) for t, r in samples if t0 <= t <= t1]
    if len(inside) < 2:
        return None
    return inside[0], inside[-1]


def measure(job, traffic, seconds, start_timeout=900.0, warmup_check=None):
    """Drive one job through warm-up and the window.

    traffic keys read here: workers, warmup_records.

    warmup_check() is called about once a second until the window opens
    and may raise (the worker opened the wrong device: no point waiting).

    Returns a dict: samples [(t, records_done)] at every observed
    change, t0/t1 (window start and end), last (the last status seen,
    paired with worker_series: the first worker's /metrics at the end of
    the window).
    """
    workers = int(traffic.get("workers", 1))
    warmup = int(traffic["warmup_records"])
    samples = []
    last_records = 0
    t0 = t1 = None
    last = None
    start_deadline = time.time() + start_timeout
    silent_since = None
    next_check = 0.0
    while True:
        s = job.status()
        now = time.time()
        if t0 is None and warmup_check and now >= next_check:
            warmup_check()
            next_check = now + 1.0
        if s is None:
            if not job.alive():
                raise RuntimeError("the job ended before it was measured")
            silent_since = silent_since or now
            if now - silent_since > 60 and last is not None:
                raise RuntimeError("the master stopped answering")
            if now > start_deadline and last is None:
                raise RuntimeError("the master never answered")
            time.sleep(0.1)
            continue
        silent_since = None
        last = status_dict(s)
        if last["records_done"] != last_records:
            last_records = last["records_done"]
            samples.append((now, last_records))
        if last["job_failed"]:
            raise RuntimeError("the master reports job_failed")
        if t0 is None:
            if now > start_deadline:
                raise RuntimeError("the job never finished warming up")
            if last_records >= warmup and last["alive_workers"] >= workers:
                t0, t1 = now, now + seconds
        elif now >= t1:
            break
        if last["finished"]:
            if t0 is None:
                raise RuntimeError(
                    "the job finished during warm-up "
                    f"({last_records} records)")
            raise RuntimeError(
                "the job ran out of records inside the window "
                f"({last_records} done): the run fails, it is not shortened")
        time.sleep(POLL_SECONDS)
    series = job.scrape("worker-0")
    s = job.status()  # paired with the scrape
    if s is not None:
        last = status_dict(s)
    return {
        "samples": samples, "t0": t0, "t1": t1, "last": last,
        "worker_series": series,
    }
