"""pages layer (compile/pages.py): host-to-device page bytes
(``PageStats.bytes_h2d``) over the window, in KiB per completed
estimate."""


def read(w):
    if not w.completed:
        return None
    return w.counters["bytes_h2d"] / 1024.0 / len(w.completed)
