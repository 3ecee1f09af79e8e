"""The program's ``dlrover_compile_requests_total`` when the run ends:
compilation requests to the backend since ``worker.init()``, served by
the persistent cache or not (set-up's included: each costs set-up time
even when cached). The registry's counter, fed by the program's own
listener for jax's ``backend_compile_duration`` event."""

from dlrover_tpu.observability.registry import get_registry

NAME = "dlrover_compile_requests_total"


def read(ctx):
    if not ctx.get("job"):
        return None
    for line in get_registry().render().splitlines():
        if line.startswith(NAME + " "):
            return float(line.split()[1])
    return None
