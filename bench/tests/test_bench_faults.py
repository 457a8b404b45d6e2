"""A run whose timed path is broken underneath comes out not correct: the
device check is skipped and the rest of the run is driven, at a tiny size,
once for each fault the cell can have."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny


def run(cell):
    return tiny.driver(cell).run(tiny.ctx(cell))


# --- fold: the server step -------------------------------------------------

FOLDS = ["fold.olmo1b.silos16", "fold.resnet18s.c1000"]


@pytest.mark.parametrize("cell", FOLDS)
def test_fold_returning_its_state_unchanged(monkeypatch, cell):
    import repro.fed.simulation as sim

    real, first = sim.broadcast_blob, []

    def stale(params, cfg):
        first.append(real(params, cfg))
        return first[0]

    monkeypatch.setattr(sim, "broadcast_blob", stale)
    assert not tiny.correct(run(cell))


@pytest.mark.parametrize("cell", FOLDS)
def test_fold_leaving_out_half_the_batch(monkeypatch, cell):
    import repro.fed as fed

    class Half(fed.Aggregator):
        def add(self, blob, weight):
            self._seen = getattr(self, "_seen", 0) + 1
            if self._seen % 2:
                super().add(blob, weight)

    monkeypatch.setattr(fed, "Aggregator", Half)
    assert not tiny.correct(run(cell))


@pytest.mark.parametrize("cell", FOLDS)
def test_fold_altering_its_answer(monkeypatch, cell):
    import repro.fed.simulation as sim

    real = sim.broadcast_blob

    def altered(params, cfg):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        leaves[-1] = leaves[-1] * 1.01
        return real(jax.tree_util.tree_unflatten(treedef, leaves), cfg)

    monkeypatch.setattr(sim, "broadcast_blob", altered)
    assert not tiny.correct(run(cell))


# --- round: the client QAT step and the server ------------------------------


def _patch_qat(monkeypatch, wrap):
    import repro.fed.simulation as sim

    real = sim.make_local_steps

    def make(apply_fn, optimizer, cfg):
        fp, qat = real(apply_fn, optimizer, cfg)
        return fp, wrap(qat)

    monkeypatch.setattr(sim, "make_local_steps", make)


def test_round_step_returning_its_state_unchanged(monkeypatch):
    _patch_qat(monkeypatch, lambda qat: lambda p, w, o, x, y: (p, w, o, jnp.zeros(())))
    assert not tiny.correct(run("round.resnet18s.noniid"))


def test_round_leaving_out_half_the_batch(monkeypatch):
    _patch_qat(monkeypatch, lambda qat: lambda p, w, o, x, y: qat(
        p, w, o, x[: x.shape[0] // 2], y[: y.shape[0] // 2]))
    assert not tiny.correct(run("round.resnet18s.noniid"))


def _patch_batches(monkeypatch, cut):
    from repro.data.federated import ClientDataset

    real = ClientDataset.batches

    def batches(self, batch_size, rng, epochs=1):
        yield from cut(real, self, batch_size, rng, epochs)

    monkeypatch.setattr(ClientDataset, "batches", batches)


@pytest.mark.parametrize("fault", ["half_batches", "one_epoch", "repeated_row"])
def test_round_training_on_other_batches(monkeypatch, fault):
    """The program's batch path hands out other work than the traffic
    asks for; the reference repeats what was recorded, so the tape check
    has to catch it."""
    def cut(real, self, batch_size, rng, epochs):
        if fault == "one_epoch":
            yield from real(self, batch_size, rng, 1)
            return
        for xb, yb in real(self, batch_size, rng, epochs):
            if fault == "half_batches":
                yield xb[: len(xb) // 2], yb[: len(yb) // 2]
            else:   # a row of the client's batch replaced by another of its rows
                yield np.concatenate([xb[1:2], xb[1:]]), np.concatenate([yb[1:2], yb[1:]])

    _patch_batches(monkeypatch, cut)
    res = run("round.resnet18s.noniid")
    assert res["compared"]["tape_off"]["value"] > 0
    assert not tiny.correct(res)


def test_round_altering_its_answer(monkeypatch):
    import repro.fed.simulation as sim

    class Altered(sim.Aggregator):
        def finalize(self, **kw):
            out = super().finalize(**kw)
            leaves, treedef = jax.tree_util.tree_flatten(out)
            leaves[0] = -leaves[0]
            return jax.tree_util.tree_unflatten(treedef, leaves)

    monkeypatch.setattr(sim, "Aggregator", Altered)
    assert not tiny.correct(run("round.resnet18s.noniid"))


def test_round_broadcasting_its_first_global_every_round(monkeypatch):
    """A fault that first shows from the second round on: every round
    starts from the first broadcast. Only the window's round can see it."""
    import repro.fed.simulation as sim

    real, first = sim.broadcast_blob, []

    def stale(params, cfg):
        first.append(real(params, cfg))
        return first[0]

    monkeypatch.setattr(sim, "broadcast_blob", stale)
    res = run("round.resnet18s.noniid")
    assert res["compared"]["change_gap"]["value"] <= res["compared"]["change_gap"]["limit"]
    assert not tiny.correct(res)


# --- decode: the served tokens ----------------------------------------------


def test_decode_step_returning_its_cache_unchanged(monkeypatch):
    import repro.launch.serve as serve

    real = serve.decode_step
    monkeypatch.setattr(serve, "decode_step", lambda cfg, params, tok, cache, pos, **kw: (
        real(cfg, params, tok, cache, pos, **kw)[0], cache))
    assert not tiny.correct(run("decode.olmo1b.b8"))


def test_decode_altering_a_token(monkeypatch):
    import repro.launch.serve as serve

    real = serve.generate

    def altered(cfg, params, prompts, gen, vision=None):
        out = np.array(real(cfg, params, prompts, gen, vision))
        out[0, -1] = (out[0, -1] + 1) % cfg.vocab_size
        return jnp.asarray(out)

    monkeypatch.setattr(serve, "generate", altered)
    assert not tiny.correct(run("decode.olmo1b.b8"))
