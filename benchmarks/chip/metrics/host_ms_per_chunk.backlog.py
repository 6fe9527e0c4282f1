"""Host time of the engine per decode chunk, in ms: wall seconds inside
`run()` not spent blocked on device results (`PagedStats.run_wall_s` -
`device_wait_s`) over `chunks`, over the window. It covers admission,
ledger growth, dispatch and the token scatter, and leaves out the harness's
own work between calls."""


def read(rec):
    s = rec.counts.get("stats") or {}
    run, wait, n = s.get("run_wall_s"), s.get("device_wait_s"), \
        s.get("chunks")
    return 1e3 * (run - wait) / n \
        if run is not None and wait is not None and n else None
