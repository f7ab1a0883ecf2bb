"""Mean time of placing one dispatch's shards on the mesh's chips (span
``mesh.put`` around ``device_put_args``, inside ``verify.launch``): five
arrays, each cut into one piece a chip."""

from benchmarks import spans

NAME, UNIT, BETTER = "mesh_put_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "executable", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "mesh.put")
