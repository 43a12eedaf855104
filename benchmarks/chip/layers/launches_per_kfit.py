"""dispatch layer (serverless/backends.py, dispatch.py): device
launches (``CompileStats.launches``) per 1,000 nuisance fits completed
in the window."""


def read(w):
    if not w.fits:
        return None
    return w.counters["launches"] / (w.fits / 1000.0)
