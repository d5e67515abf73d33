"""Host time of Runtime.deploy and the engine's construction, with the
weights made on the device from the seed."""


def read(run):
    return run.rec.deploy_s
