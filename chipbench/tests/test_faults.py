"""The comparison that decides ``correct`` fails a broken timed path, and
the precision control serves from the program's own int8 packs through
the same comparison (CPU, tiny size; at this size int8 weights move too
few tokens to fail it, which the chip shows at the cells' sizes)."""
import jax.numpy as jnp
import pytest

from chipbench.tests import _tiny


def _altered_token(orig):
    def step(self, *a, **k):
        last, pages = orig(self, *a, **k)
        return (last + 1) % self.cfg.vocab_size, pages
    return step


def _state_unchanged(orig):
    # the decode step writes nothing and hands back the tokens it got
    def step(self, pages, table, lens, mask, last, **k):
        return orig(self, pages, table, lens, jnp.zeros_like(mask), last,
                    **k)
    return step


def _half_batch(orig):
    # the decode step runs every other live slot and leaves the rest out
    def step(self, pages, table, lens, mask, last, **k):
        return orig(self, pages, table, lens, mask.at[1::2].set(False), last,
                    **k)
    return step


@pytest.mark.parametrize("fault,traffic",
                         [(_altered_token, "chat"), (_state_unchanged, "chat"),
                          (_half_batch, "prefill_sat")],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_a_broken_decode_step_is_not_correct(monkeypatch, fault, traffic):
    from repro.runtime.serve_loop import Engine
    monkeypatch.setattr(Engine, "decode_step", fault(Engine.decode_step))
    r = _tiny.run(traffic)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_a_broken_prefill_token_is_not_correct(monkeypatch):
    from repro.runtime.serve_loop import Engine
    monkeypatch.setattr(Engine, "prefill_chunk",
                        _altered_token(Engine.prefill_chunk))
    r = _tiny.run("prefill_sat")
    assert r["correct"] is False


def test_the_program_serves_from_its_own_int8_packs(monkeypatch):
    from chipbench import bench
    from repro.quant.formats import QuantizedPackedWeight
    seen = []
    build = bench.build

    def spy(*a, **k):
        eng = build(*a, **k)
        seen.append(eng.params["layers"]["ffn"]["w_gate_up"])
        return eng
    monkeypatch.setattr(bench, "build", spy)
    r = _tiny.run("prefill_sat", quant="int8")
    assert isinstance(seen[0], QuantizedPackedWeight)
    assert seen[0].fmt == "int8"
    assert r["checks"]["compared_tokens"]["value"] >= 48
    assert r["checks"]["mean_gap"]["value"] is not None
