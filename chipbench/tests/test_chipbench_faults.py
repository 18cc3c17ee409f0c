"""Each run's correctness check catches the faults its cell can have: the
harness is driven as in a real run (past the look for a chip), with the
timed path broken underneath, and ``correct`` must come out false.  Smoke
widths on the CPU."""
import jax
import jax.numpy as jnp

from chipbench.tests.test_chipbench_harness import execute, serve_smoke, train_smoke


def _failed(line):
    """Checks that failed; a reading of NaN or infinity is shown as text."""
    return [k for k, c in line["checks"].items()
            if isinstance(c["value"], str) or not abs(c["value"]) <= c["limit"]]


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from repro.runtime import server
    real = server.Server._sample
    calls = []

    def altered(self, logits, key):
        tok = real(self, logits, key)
        calls.append(1)
        if len(calls) % 4 == 3:          # one token in four, after the prompt
            tok = (tok + 1) % self.run_cfg.model.vocab_size
        return tok

    monkeypatch.setattr(server.Server, "_sample", altered)
    line = execute(serve_smoke(), monkeypatch)
    assert line["correct"] is False
    assert {"max_gap", "mean_gap"} & set(_failed(line))


def _broken_bundle(monkeypatch, make_step):
    """Replace the program's train step, under the probe, by ``make_step``."""
    from repro.runtime import steps
    real = steps.train_bundle

    def bundle(rc, mesh=None):
        b = real(rc, mesh)
        good = b.jit()

        class Broken:
            def jit(self):
                return make_step(rc, good)
        return Broken()

    monkeypatch.setattr(steps, "train_bundle", bundle)


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    def make_step(rc, good):
        def step(state, batch):
            new, metrics = good(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return step

    _broken_bundle(monkeypatch, make_step)
    line = execute(train_smoke(), monkeypatch)
    assert line["correct"] is False
    assert {"grad_gap", "change_gap"} <= set(_failed(line))


def test_train_half_of_the_batch_left_out(monkeypatch):
    def make_step(rc, good):
        def step(state, batch):
            half = {k: jnp.concatenate([v[: len(v) // 2]] * 2) for k, v in batch.items()}
            return good(state, half)
        return step

    _broken_bundle(monkeypatch, make_step)
    line = execute(train_smoke(), monkeypatch)
    assert line["correct"] is False
    assert "grad_gap" in _failed(line)


def test_train_exchange_between_chips_left_out(monkeypatch):
    """FSDP with no gradient exchange: the shard of each weight that chip k
    holds is updated with the gradient of chip k's rows alone.  Emulated on
    one device for four chips."""
    from repro.models import build_model
    from repro.optim import adamw_update, warmup_cosine

    chips = 4

    def make_step(rc, good):
        model = build_model(rc.model, rc.sharding)
        axes = model.axes()
        lr_fn = warmup_cosine(rc.train)
        grad = jax.value_and_grad(lambda p, b: model.loss(p, b)[0])

        @jax.jit
        def step(state, batch):
            rows = batch["tokens"].shape[0] // chips
            parts = [grad(state.params, {k: v[i * rows:(i + 1) * rows]
                                         for k, v in batch.items()})
                     for i in range(chips)]

            def own_shard(ax, *gs):
                if "fsdp" not in ax:
                    return gs[0]
                dim = ax.index("fsdp")
                return jnp.concatenate(
                    [jnp.array_split(g, chips, axis=dim)[i] for i, g in enumerate(gs)],
                    axis=dim)

            grads = jax.tree.map(own_shard, axes, *[g for _, g in parts],
                                 is_leaf=lambda x: isinstance(x, tuple))
            new, metrics = adamw_update(state, grads, rc.train, lr_fn)
            loss = jnp.mean(jnp.stack([l for l, _ in parts]))
            return new, dict(metrics, loss=loss)
        return step

    _broken_bundle(monkeypatch, make_step)
    line = execute(train_smoke(), monkeypatch)
    assert line["correct"] is False
    assert "grad_gap" in _failed(line)
