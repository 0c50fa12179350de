"""phase1_ms.single: device ms of one jitted `phase1_device` call (every
stage before the recovery tail) on each graph of the traced window, made
after that window while the profiler still records, read from its own
module's events and averaged."""
MODULE = "jit_phase1_device"


def _call(g):
    import jax
    import jax.numpy as jnp

    from repro.core.sparsify import phase1_device

    jax.block_until_ready(phase1_device(
        jnp.asarray(g.u, jnp.int32), jnp.asarray(g.v, jnp.int32),
        jnp.asarray(g.w, jnp.float32), g.n))


def _graphs(run):
    seen, out = set(), []
    for c in run.traced:
        if c.index not in seen:
            seen.add(c.index)
            out.extend(run.pool[c.index][0])
    return out


def prepare(run):
    """Compile (or load) the phase-1 program in set-up."""
    _call(run.pool[0][0][0])


def after_window(run):
    for g in _graphs(run):
        _call(g)


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_s(MODULE)
    return 1e3 * sum(times) / len(times) if times else None
