"""Process start to the first due request: imports, deploy, the weights
made on the device, and the first call of each compiled step."""


def read(run):
    return run.rec.setup_s
