"""Host runtime: backend compilations or persistent-cache loads
(jax.compile spans) over the traced run, window and drain.  Set-up
compiles both engine steps, so any count is a recompilation.  None where
the run holds no serve.step span (no traced window, or a program that
records no runtime spans)."""


def read(run):
    spans = run.data.get("spans", [])
    if not any(name == "serve.step" for name, _, _ in spans):
        return None
    return sum(1 for name, _, _ in spans if name == "jax.compile")
