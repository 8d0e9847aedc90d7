"""setup_s: seconds from process start to the window's opening: imports,
graph and order load, the orderer's commit, the stream generator, warm-up
(host clock)."""


def read(run):
    return run.setup_s
