"""Rate of stage 1's copy of frames and dark frame to the device, in GB/s:
the bytes that the program's ``hedm.to_device`` spans carry over their
seconds inside the window. A span ends when the arrays are on the device."""
import host_spans


def read(run):
    return host_spans.gb_per_s(host_spans.of(run), "hedm.to_device")
