"""Peak device memory in use over the whole process, warm-up included:
the device runtime's own counter (``memory_stats()["peak_bytes_in_use"]``),
read after the window.  ``BENCHMARK.json`` names its source
``device_trace``, the one device-side source an end-to-end metric may
name."""


def read(rec):
    peak = rec["memory_peak_bytes"]
    return None if not peak else peak / 2 ** 30
