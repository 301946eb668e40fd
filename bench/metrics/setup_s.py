"""Set-up time: process start to the window's opening (data, index build,
compilation or compile-cache loads, warm-up), on the host clock."""


def read(rec):
    return rec["setup_s"]
