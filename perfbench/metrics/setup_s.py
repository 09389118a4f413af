"""Set-up (s): process start to the first timed call, with imports,
init, on-device inputs, warm-up and any compilation."""


def read(r):
    return r.setup_s
