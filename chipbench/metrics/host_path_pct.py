"""Share of the serve worker's busy (not 'idle') time spent outside
'device_wait': the host path that runs in series with the chip, from the
phase spans of the window."""
import phases


def read(run):
    secs = phases.seconds_by_phase(run)
    busy = sum(s for p, s in secs.items() if p != "idle")
    if not busy:
        return None
    return 100.0 * (busy - secs.get("device_wait", 0.0)) / busy
