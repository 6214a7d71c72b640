"""Host-to-device bytes per cadence: the sum over tenants of the solve
reports' `upload_bytes` (service/engine.py), averaged over the window's
cadences."""


def read(rec):
    up = rec["run"].get("upload_bytes") if rec["run"].get("driver") == "service" else None
    if not up:
        return None
    return sum(up) / len(up)
