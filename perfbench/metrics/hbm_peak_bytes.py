"""The device allocator's ``peak_bytes_in_use`` after the window: the most
device memory the run held at once."""


def read(ctx):
    return ctx.memory_peak_bytes
