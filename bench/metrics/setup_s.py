"""Process start to the window's opening: imports, weights, engine build
(energy-model weight walk, packing), warm-up and any compile."""


def read(r):
    return r.served.setup["setup_s"]
