"""setup_s: process start to the window's first request (s), host clock:
building the service and its keys, loading or compiling every shape
the mix uses, warming them, and making the run's inputs."""


def read(r):
    return r.setup_s
