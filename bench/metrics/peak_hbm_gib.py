"""Peak device memory in GiB on the fullest chip (``run.peak_estimate``):
the bytes in use after the window (``memory_stats()["bytes_in_use"]``)
plus the most any one program of the window adds while it runs (its
outputs that do not alias an argument and its temporaries, from the
compiler's ``memory_analysis()``), or the allocator's
``peak_bytes_in_use`` where that is larger. On the TPU the allocator's
peak leaves out the programs' temporaries."""


def read(ctx):
    if ctx.peak_bytes is None:
        return None
    return ctx.peak_bytes / 2 ** 30
