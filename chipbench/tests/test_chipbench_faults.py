"""With the timed path broken underneath, the rest of a run goes on and
``correct`` comes out false: for each fault a cell can have."""
import jax
import numpy as np
import pytest

from chipbench.tests.test_chipbench_drivers import execute


def _step_keeps_state(monkeypatch):
    from repro.core.mesh_engine import ElasticMeshSGD
    step = ElasticMeshSGD.step

    def unchanged(self, batch, *a, **k):
        state = self.state
        out = step(self, batch, *a, **k)
        self.state = state
        return out
    monkeypatch.setattr(ElasticMeshSGD, "step", unchanged)


def _half_batch(monkeypatch):
    from repro.core.mesh_engine import ElasticMeshSGD
    mask = ElasticMeshSGD.work_mask

    def half(self, seq_len):
        m = mask(self, seq_len)
        return m.at[m.shape[0] // 2:].set(0.0)   # mean over the rest
    monkeypatch.setattr(ElasticMeshSGD, "work_mask", half)


def _altered_state(monkeypatch):
    from repro.core.mesh_engine import ElasticMeshSGD
    step = ElasticMeshSGD.step

    def altered(self, batch, *a, **k):
        out = step(self, batch, *a, **k)
        p = self.state["params"]
        p["final_norm"]["scale"] = p["final_norm"]["scale"] + 0.05
        return out
    monkeypatch.setattr(ElasticMeshSGD, "step", altered)


def _reducer_keeps_state(monkeypatch):
    from repro.core.reducer import MasterReducer
    monkeypatch.setattr(MasterReducer, "reduce_and_step",
                        lambda self, msgs, **k: self.params)


def _reducer_half_workers(monkeypatch):
    from repro.core.reducer import MasterReducer
    step = MasterReducer.reduce_and_step

    def half(self, msgs, **k):
        names = sorted(msgs)[:len(msgs) // 2]
        return step(self, {w: msgs[w] for w in names}, **k)
    monkeypatch.setattr(MasterReducer, "reduce_and_step", half)


def _reducer_altered_values(monkeypatch):
    from repro.core import reducer
    kernel = reducer.fused_block_topk_batched

    def altered(g, r, **k):
        vals, idx, res = kernel(g, r, **k)
        return vals + 1e-2, idx, res
    monkeypatch.setattr(reducer, "fused_block_topk_batched", altered)


def _served_token_altered(monkeypatch):
    from repro.serving.engine import ServingEngine
    step = ServingEngine.step

    def altered(self, now=None):
        rep = step(self, now)
        for c in rep.completed:
            c.tokens = np.asarray(c.tokens).copy()
            c.tokens[-1] = (c.tokens[-1] + 1) % self.cfg.vocab_size
        return rep
    monkeypatch.setattr(ServingEngine, "step", altered)


FAULTS = [
    ("train.lm100m", _step_keeps_state),
    ("train.lm100m", _half_batch),
    ("train.lm100m", _altered_state),
    ("reduce.lm100m.w4", _reducer_keeps_state),
    ("reduce.lm100m.w4", _reducer_half_workers),
    ("reduce.lm100m.w4", _reducer_altered_values),
    ("serve.qwen3-4b.chat", _served_token_altered),
]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__[1:]}" for n, f in FAULTS])
def test_a_broken_path_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    jax.clear_caches()
    line, err = execute(name)
    assert line["correct"] is False, err
    jax.clear_caches()
