"""Queries completed in the window over the window's wall seconds."""


def read(record):
    if record["drive"] != "serve":
        return None
    return len(record["comps"]) / record["window_s"]
