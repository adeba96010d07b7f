"""Checks that make a run a failed run (non-zero exit, no result line):
no chip, a plan demoted to the reference executor, a retrieve program
without a compiled Pallas kernel. Taken from ``chip_smoke.py``."""

from __future__ import annotations


class RunFailure(RuntimeError):
    """The run cannot give a result."""


def require_tpu(count: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunFailure(
            f"no TPU: JAX found {len(devices)} {devices[0].platform} device(s); "
            "the benchmark runs only on the chip"
        )
    if len(devices) < count:
        raise RunFailure(f"need {count} TPU chips, JAX found {len(devices)}")
    return devices[:count]


def check_no_fallback(server) -> None:
    """However well a demoted server still answers, it is not the path
    under test."""
    plan = server.plan
    if plan.config.executor != "kernel":
        raise RunFailure(f"plan resolved executor={plan.config.executor!r}, not 'kernel'")
    if plan.fallback_active:
        raise RunFailure("plan fell back to the reference executor")


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _pallas_calls(sub)


def _subjaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def kernel_calls(server, q, qmask) -> int:
    """Compiled (not interpreted) Pallas calls in the program the server
    dispatches for a batch ``q``, as traced through its plan."""
    import jax

    closed = jax.make_jaxpr(server.plan.retrieve_batch)(q, qmask)
    return sum(not e.params.get("interpret", False) for e in _pallas_calls(closed.jaxpr))


def check_kernel_in_program(server, q, qmask) -> None:
    if kernel_calls(server, q, qmask) == 0:
        raise RunFailure("the retrieve program has no compiled Pallas kernel")
