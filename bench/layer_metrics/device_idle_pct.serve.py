"""Share of the serving window in which no operation ran on the device."""


def read(record):
    if record["drive"] != "serve":
        return None
    tr = record["trace"]
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
