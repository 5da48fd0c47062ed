"""Edge-axis sharding rules: logical axes -> mesh axes."""
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as SH


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_spec_filters_nondivisible_dims():
    mesh = _FakeMesh({"pod": 2, "data": 4, "model": 2})
    # 64 edges split over all three axes (2 * 4 * 2 = 16 shards)
    assert SH.spec_for(("edge",), (64,), mesh=mesh) == P(("pod", "data", "model"))
    # 12 edges: "pod" divides, "data" would make 8 shards and is dropped,
    # "model" makes 4 and is kept
    assert SH.spec_for(("edge", None), (12, 2), mesh=mesh) == P(("pod", "model"), None)
    # an odd edge count takes no axis
    assert SH.spec_for(("edge",), (7,), mesh=mesh) == P(None)
    # rule axes the mesh lacks are skipped
    assert SH.spec_for(("edge",), (8,), mesh=_FakeMesh({"data": 4})) == P("data")


def test_spec_never_reuses_mesh_axis():
    mesh = _FakeMesh({"data": 4, "model": 2})
    rules = {"edge": ("data", "model"), "word": "model"}
    # "edge" takes both axes; "word" must not reuse "model"
    assert SH.spec_for(("edge", "word"), (8, 8), mesh=mesh,
                       rules=rules) == P(("data", "model"), None)
    # 12 edges cannot take 4 * 2 shards, so "model" stays free for "word"
    assert SH.spec_for(("edge", "word"), (12, 8), mesh=mesh,
                       rules=rules) == P("data", "model")
    # without dim sizes the first claim wins outright
    assert SH.spec_for(("edge", "edge"), mesh=mesh) == P(("data", "model"), None)


def test_use_rules_installs_and_restores_mesh():
    """``use_rules`` installs the mesh the engine's edge split reads, per
    thread and nested, and puts the previous one back on exit, also when
    the block raises."""
    import threading

    outer, inner = _FakeMesh({"data": 2}), _FakeMesh({"data": 4})
    assert SH.active_mesh() is None
    with SH.use_rules(outer):
        assert SH.active_mesh() is outer
        seen = []
        worker = threading.Thread(target=lambda: seen.append(SH.active_mesh()))
        worker.start()
        worker.join()
        assert seen == [None]
        try:
            with SH.use_rules(inner):
                assert SH.active_mesh() is inner
                assert SH.spec_for(("edge",), (8,)) == P("data")
                raise RuntimeError
        except RuntimeError:
            pass
        assert SH.active_mesh() is outer
    assert SH.active_mesh() is None
