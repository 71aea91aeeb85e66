"""Process start to window start: data, set-up training, warm-up and the
compiles or compile-cache loads among them."""


def read(record):
    return record["setup_s"]
