"""Set-up seconds: process start to the first due request (weights drawn,
engine built, every step program compiled or loaded)."""


def read(run):
    return run["setup_s"]
