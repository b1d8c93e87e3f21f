"""Rate of stage 1's copy of masks and signal counts back to the host, in
GB/s: the bytes that the program's ``hedm.from_device`` spans carry over
their seconds inside the window. The filter has ended on the device before
a span starts."""
import host_spans


def read(run):
    return host_spans.gb_per_s(host_spans.of(run), "hedm.from_device")
