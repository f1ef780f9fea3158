"""Process start to the opening of the window: weights, calibration,
quantization, server start and warm-up, with whatever they compile."""


def read(run):
    return run.setup_s
