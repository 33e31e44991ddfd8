"""setup_s: process start to the window's opening (the first request due),
weights, compilation or its cache, warm-up and the server's start included."""


def read(rec):
    return rec["setup_s"]
