"""HBM held by live arrays after the window, on the fullest chip, in GiB:
the device allocator's ``peak_bytes_in_use``. That is the parameters, the
optimizer state and the batch; the step program's temporaries are not in
it (the v5e runtime counts them under ``bytes_reserved``), so it reads
within 0.1 GiB of the step's argument bytes as the compiler gives them."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 2 ** 30 if b else None
