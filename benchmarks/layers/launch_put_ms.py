"""Mean time of one launch's five host-to-device transfers on one chip (span
``verify.launch.put``: five ``jnp.asarray``); the second of ``launch_ms``'s three
parts.  A mesh-wide launch places its shards under ``mesh.put``
(``mesh_put_ms``)."""

from benchmarks import spans

NAME, UNIT, BETTER = "launch_put_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "executable", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.launch.put")
