"""The program's ``dlrover_compile_requests_total`` when the run ends:
compilation requests to the backend since ``worker.init()``, served by
the persistent cache or not (set-up's included: each costs set-up time
even when cached). The registry's counter, fed by the program's own
listener for jax's ``backend_compile_duration`` event. A job that runs the
program in child processes hands the worker's rendered registry back as
``ctx["registry_text"]``."""

NAME = "dlrover_compile_requests_total"


def read(ctx):
    if not ctx.get("job"):
        return None
    text = ctx.get("registry_text")
    if text is None:
        from dlrover_tpu.observability.registry import get_registry

        text = get_registry().render()
    for line in text.splitlines():
        if line.startswith(NAME + " "):
            return float(line.split()[1])
    return None
