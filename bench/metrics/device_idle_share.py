"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of device op intervals / window)."""


def read(view):
    if not view.planes():
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
