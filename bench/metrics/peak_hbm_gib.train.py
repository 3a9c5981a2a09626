"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
read after the window, in GiB: on several chips, the fullest chip's."""


def read(rec):
    b = rec.get("peak_bytes")
    return b / 2**30 if b else None
