"""Peak bytes of the fullest chip (``peak_bytes_in_use`` + ``peak_bytes_reserved`` of
``device.memory_stats()``: buffers and the programs' temporaries), read when
the window has closed and before the reference runs."""


def read(context):
    if not context["memory_peak_bytes"]:
        return None
    return context["memory_peak_bytes"] / 1e9
