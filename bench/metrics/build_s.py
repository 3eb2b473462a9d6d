"""build_s: wall seconds of ``AnnIndex.build(corpus, cfg)``, ended by
``block_until_ready`` on the built index."""


def read(run):
    return run.build_s
