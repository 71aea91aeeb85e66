"""Training episodes completed in the window over its wall seconds."""


def read(record):
    if record["drive"] != "train":
        return None
    return len(record["logs"]) / record["window_s"]
