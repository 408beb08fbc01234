"""build_s: host clock around `LCCSIndex.build`, ending when every array of
the index is ready on the device."""


def read(run):
    return run.build_s
