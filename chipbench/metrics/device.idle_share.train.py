"""device.idle_share.train (%): 1 - (union of the device-op intervals)
over the traced window. Layer: device. Source: device trace. Moves
train_tokens_per_s."""


def read(view):
    return 100.0 * view.summary.idle_share
