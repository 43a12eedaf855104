"""pages layer (compile/pages.py): bytes of the ``pages.fetch`` spans
whose ``source`` is ``d2d`` — pages moved from one chip's pool to
another's, as a steal moves them — in KiB per completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None or not prog.count("pages.fetch") or not w.completed:
        return None
    nbytes = sum(s[6]["bytes"] for s in prog.named("pages.fetch")
                 if s[6]["source"] == "d2d")
    return nbytes / 1024.0 / len(w.completed)
