"""qps (queries/s): the queries answered in the window over the window's
length.  Answers come in bursts, one per served batch, so the batch in
service at the close counts by the share of its time that lies inside the
window (`RunRecord.answered`); refused requests never count."""


def read(run):
    return run.answered(run.seconds) / run.seconds
