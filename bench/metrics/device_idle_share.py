"""Device idle share (%): 1 - (union of the device's op intervals / the
traced window), the window running from the first batch's start to the
last batch's end."""


def read(view):
    red = view.trace
    if red.window_s <= 0 or not red.busy:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
