import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn`` under ``jax.profiler`` and returns the
    ``repro.*`` host spans the trace holds: ``{name: [(start, end)]}`` in
    nanoseconds."""
    import jax

    def run(fn):
        out = tmp_path / "profile"
        jax.profiler.start_trace(str(out))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = out.rglob("*.xplane.pb")
        spans = {}
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
        return spans

    return run
