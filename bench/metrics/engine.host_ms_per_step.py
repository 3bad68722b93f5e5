"""Engine loop (``launch/engine.py``): host milliseconds per observed
step that are not the wait for the step's tokens, as
``summary()["host_ms_mean"]`` computes it, over the steps of the traced
window. Moves ``itl_p50_ms``."""


def read(run):
    step_s, dev_s = run.engine_step_s, run.engine_device_s
    if not step_s:
        return None
    return 1e3 * (sum(step_s) / len(step_s) - sum(dev_s) / len(dev_s))
