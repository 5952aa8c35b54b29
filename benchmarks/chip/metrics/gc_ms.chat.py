"""Host runtime: milliseconds of Python garbage collection (py.gc spans)
over the traced run, window and drain.  None where the run holds no
serve.step span (no traced window, or a program that records no runtime
spans)."""


def read(run):
    spans = run.data.get("spans", [])
    if not any(name == "serve.step" for name, _, _ in spans):
        return None
    return 1e3 * sum(dur for name, _, dur in spans if name == "py.gc")
