"""Rate of the copy of masks and signal counts back from all the chips to
the host, in GB/s: the bytes that the program's ``hedm.from_devices`` spans
carry over their seconds inside the window. The filter has ended on every
chip before a span starts."""
import host_spans


def read(run):
    return host_spans.gb_per_s(host_spans.of(run), "hedm.from_devices")
