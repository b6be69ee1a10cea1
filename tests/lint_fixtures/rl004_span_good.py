"""RL004 negative fixture (spoofed engine_jax.py rel_path): one span around
the whole call, the loop inside it."""
from repro.obs.spans import span


def unpack(rows):
    with span("repro.engine.unpack"):
        out = []
        for row in rows:
            out.append(row)
    return out
