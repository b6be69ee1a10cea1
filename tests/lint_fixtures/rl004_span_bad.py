"""RL004 positive fixture (spoofed engine_jax.py rel_path): host spans
opened inside hot-path loop bodies."""
import jax

from repro.obs.spans import span


def unpack(rows):
    out = []
    for row in rows:
        with span("repro.engine.unpack"):  # one span per instance
            out.append(row)
    return out


def drain(queue):
    while queue:
        with jax.profiler.TraceAnnotation("repro.engine.event"):
            queue.pop()
