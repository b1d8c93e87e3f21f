"""Rate at which staging sends the scan from the host to the chips, in
GB/s: the bytes that the program's ``hedm.to_devices`` spans carry (a
share of the frames to each chip, and the dark frame to each) over their
seconds inside the window. A span ends when the arrays are on the chips."""
import host_spans


def read(run):
    return host_spans.gb_per_s(host_spans.of(run), "hedm.to_devices")
