"""setup_s: process start to the first timed call (imports,
``build_service``, the traffic's data, the warm-up drains, and compilation
on a cold cache)."""


def read(ctx):
    return ctx["setup_s"]
