"""setup_s: process start to the window's start (data, reference sample
for w, index build, router, plan warm-up, compilation)."""


def read(run):
    return run.setup_s
