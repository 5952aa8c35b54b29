"""Scheduler (the engine's host loop): share of the engine steps' wall
time in which the host, not the chip, set the pace, %: 100 x (sum of
serve.step - sum of serve.prefill.wait - sum of serve.decode.wait) /
sum of serve.step.  None where the run holds no serve.step span."""


def read(run):
    walls = {"serve.step": 0.0, "serve.prefill.wait": 0.0, "serve.decode.wait": 0.0}
    for name, _, dur in run.data.get("spans", []):
        if name in walls:
            walls[name] += dur
    step = walls.pop("serve.step")
    if step <= 0:
        return None
    return 100.0 * (step - sum(walls.values())) / step
