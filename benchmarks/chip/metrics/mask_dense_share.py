"""Share of frames, in %, whose stage-1 mask came back from the chip whole
rather than as its occupied rows: the ``dense_frames`` over the ``frames``
that the program's ``hedm.from_device`` and ``hedm.from_devices`` spans
count inside the window. A program whose copy back counts no frames
gives no reading."""
import host_spans

SPANS = ("hedm.from_device", "hedm.from_devices")


def read(run):
    spans = host_spans.of(run)
    stats = [st for name in SPANS for _, st in spans.get(name, ())]
    frames = sum(int(st.get("frames", 0)) for st in stats)
    if not frames:
        return None
    return 100.0 * sum(int(st.get("dense_frames", 0))
                       for st in stats) / frames
