"""device.chip_reach_s (s): process start to the first array on the
device (importing JAX, finding the chip, one tiny program). Layer:
device. Source: host clock. Moves setup_s."""


def read(view):
    return view.marks.get("chip_reached")
