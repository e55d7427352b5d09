"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals over the window, averaged over the
chips used)."""


def read(view):
    return view.idle_pct()
