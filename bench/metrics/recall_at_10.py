"""recall_at_10: mean, over the window's answered non-probe requests, of
the share of the reference's 10 nearest found among the first 10 served
(see ``tacobench.check``)."""


def read(run):
    return run.check["recall_at_10"]
