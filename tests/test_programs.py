"""Per-rung programs compiled ahead (repro.core.programs)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import programs
from repro.core.programs import RungPrograms


def test_rungs_compiled_ahead_are_not_compiled_again():
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    ladder = (8, 16, 32, 64, 128)
    built = []

    def build(w):
        built.append(w)
        return jax.jit(lambda x, k: x[:w].sum() * k + w)

    rungs = RungPrograms(ladder, build)
    xs = np.arange(128.0)
    x = jnp.asarray(xs)
    k = jnp.asarray(2.0)
    assert float(rungs(8, x, k)(x, k)) == xs[:8].sum() * 2 + 8
    # the next AHEAD rungs were built and are compiling in the background
    assert built == list(ladder[: 1 + programs.AHEAD])
    for w in ladder[1 : 1 + programs.AHEAD]:
        rungs._ahead[w].result()
    before = len(compiles)
    for w in ladder[1 : 1 + programs.AHEAD]:
        assert float(rungs(w, x, k)(x, k)) == xs[:w].sum() * 2 + w
    # these calls queued the rest of the ladder; once it has compiled, the
    # only compiles since `before` are those rungs': none ran twice
    rest = [w for w in ladder[1 + programs.AHEAD :]]
    for w in rest:
        rungs._ahead[w].result()
    rungs.close()
    assert len(compiles) - before == len(rest)
