"""Composition closure over the parallelism families (VERDICT r4 item 6).

Every pair in {dp, fsdp, tp, sp, ep, pp} must be tested-WORKING (loss
parity vs the replicated step, like test_parallel.py's TP+FSDP) or
tested-ERRORING (a clear trace-time rejection). Coverage map — dp x
{fsdp, tp, sp, ep, pp} live in test_parallel.py/test_moe.py and the
dryrun; fsdp x tp in test_parallel.py:545. This file closes the rest:

  working: fsdp x sp, fsdp x ep, fsdp x pp, tp x ep, sp x ep
  erroring: tp x sp(ring), tp x pp, sp(ring) x pp, ep x pp

docs/parallelism.md carries the resulting matrix.
"""

import tempfile

import jax
import numpy as np
import pytest

from tensor2robot_tpu import parallel
from tensor2robot_tpu.parallel.sharding import (
    EP_RULES_MOE,
    PP_RULES_TRANSFORMER,
    TP_RULES_TRANSFORMER,
)
from tensor2robot_tpu.research.seq2act import Seq2ActBCModel
from tensor2robot_tpu.specs.struct import SpecStruct
from tensor2robot_tpu.trainer import Trainer


def _model(mesh, **overrides):
  kwargs = dict(
      episode_length=4, action_size=2, vocab_size=8, img_res=(32, 32),
      src_img_res=(36, 36), tokens_per_frame=4, embed_dim=32,
      num_layers=2, num_heads=2, head_dim=8, mlp_dim=32,
      tokenizer_widths=(8, 8, 8, 16), attention_mode='xla', mesh=mesh)
  kwargs.update(overrides)
  return Seq2ActBCModel(**kwargs)


def _one_step(model, mesh, rules=None, use_fsdp=False, batch=8):
  """One compiled train step; returns (loss, {path: spec_str})."""
  rng_np = np.random.RandomState(0)
  frames = rng_np.randint(0, 255, (batch, 4, 36, 36, 3), dtype=np.uint8)
  actions = rng_np.rand(batch, 4, 2).astype(np.float32) * 2 - 1
  features = SpecStruct(image=frames)
  labels = SpecStruct(action=actions)
  with tempfile.TemporaryDirectory() as tmp:
    trainer = Trainer(model, tmp, mesh=mesh, tp_rules=rules,
                      use_fsdp=use_fsdp, async_checkpoints=False,
                      save_checkpoints_steps=10**9)
    state = trainer.init_state(features, labels)
    step_fn = trainer._compile_train_step()
    device_batch = trainer._put_batch(
        {'features': features.to_dict(), 'labels': labels.to_dict()})
    rng = jax.device_put(
        jax.random.PRNGKey(3),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    state, metrics = step_fn(state, device_batch['features'],
                             device_batch['labels'], rng)
    shardings = {
        jax.tree_util.keystr(path): str(leaf.sharding.spec)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            state.params)[0]}
    trainer.close()
  return float(metrics['loss']), shardings


def _replicated_loss(**model_overrides):
  mesh = parallel.create_mesh({'data': 8})
  loss, _ = _one_step(_model(mesh, **model_overrides), mesh)
  return loss


class TestWorkingPairs:

  def test_tp_with_ep_matches_replicated(self):
    """data x model x expert: attention TP-sharded, MoE expert-sharded
    (the a2a shard_map), in one transformer — rule sets concatenate."""
    mesh = parallel.create_mesh({'data': 2, 'model': 2, 'expert': 2})
    moe = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    loss, shardings = _one_step(
        _model(mesh, tp_axis='model', ep_axis='expert', **moe),
        mesh, rules=TP_RULES_TRANSFORMER + EP_RULES_MOE)
    ref = _replicated_loss(**moe)
    np.testing.assert_allclose(loss, ref, rtol=2e-5)
    qkv = [s for p, s in shardings.items() if p.endswith("qkv']['kernel']")]
    assert qkv and all('model' in s for s in qkv), shardings
    w_in = [s for p, s in shardings.items() if p.endswith("'w_in']")]
    assert w_in and all('expert' in s for s in w_in), shardings

  def test_ring_with_fsdp_matches_replicated(self):
    """data x fsdp with ring attention: the seq shard_map and the FSDP
    param gathers compose."""
    mesh = parallel.create_mesh({'data': 4, 'fsdp': 2})
    loss, shardings = _one_step(
        _model(mesh, attention_mode='ring',
               tokenizer_widths=(8, 8, 8, 256)),
        mesh, use_fsdp=True)
    ref = _replicated_loss(attention_mode='ring',
                           tokenizer_widths=(8, 8, 8, 256))
    np.testing.assert_allclose(loss, ref, rtol=2e-5)
    assert any('fsdp' in s for s in shardings.values()), shardings

  def test_ep_with_fsdp_matches_replicated(self):
    mesh = parallel.create_mesh({'data': 2, 'expert': 2, 'fsdp': 2})
    moe = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0,
               tokenizer_widths=(8, 8, 8, 256))
    loss, shardings = _one_step(
        _model(mesh, ep_axis='expert', **moe), mesh,
        rules=EP_RULES_MOE, use_fsdp=True)
    ref = _replicated_loss(**moe)
    np.testing.assert_allclose(loss, ref, rtol=2e-5)
    w_in = [s for p, s in shardings.items() if p.endswith("'w_in']")]
    assert w_in and all('expert' in s for s in w_in), shardings
    assert any('fsdp' in s for s in shardings.values()), shardings

  def test_pp_with_fsdp_matches_replicated(self):
    mesh = parallel.create_mesh({'data': 2, 'pipe': 2, 'fsdp': 2})
    loss, shardings = _one_step(
        _model(mesh, pipe_axis='pipe', pipeline_microbatches=2,
               tokenizer_widths=(8, 8, 8, 256)),
        mesh, rules=PP_RULES_TRANSFORMER, use_fsdp=True)
    # Baseline: the SAME pipelined model on a pipe-size-1 mesh (data-only)
    # — a non-pipelined stack has a different param-init rng tree (stacked
    # pipe_blocks init), so its loss is not comparable.
    ref_mesh = parallel.create_mesh({'data': 8})
    ref, _ = _one_step(
        _model(ref_mesh, pipe_axis='pipe', pipeline_microbatches=2,
               tokenizer_widths=(8, 8, 8, 256)),
        ref_mesh, rules=PP_RULES_TRANSFORMER)
    np.testing.assert_allclose(loss, ref, rtol=2e-5)
    pipe = [s for p, s in shardings.items() if 'pipe_blocks' in p]
    assert pipe and all('pipe' in s for s in pipe), shardings
    assert any('fsdp' in s for s in shardings.values()), shardings

  def test_ring_with_ep_matches_replicated(self):
    """Sequence-sharded attention + expert-sharded MoE in one block
    stack: two independent shard_maps over different axes."""
    mesh = parallel.create_mesh({'data': 2, 'expert': 4})
    moe = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    loss, shardings = _one_step(
        _model(mesh, attention_mode='ring', ep_axis='expert', **moe),
        mesh, rules=EP_RULES_MOE)
    ref = _replicated_loss(attention_mode='ring', **moe)
    np.testing.assert_allclose(loss, ref, rtol=2e-5)
    w_in = [s for p, s in shardings.items() if p.endswith("'w_in']")]
    assert w_in and all('expert' in s for s in w_in), shardings


class TestErroringPairs:
  """Unsupported combinations fail loudly at trace time, with the reason."""

  def _init(self, mesh, **overrides):
    model = _model(mesh, **overrides)
    rng = np.random.RandomState(0)
    frames = rng.randint(0, 255, (2, 4, 36, 36, 3), dtype=np.uint8)
    actions = rng.rand(2, 4, 2).astype(np.float32) * 2 - 1
    features, labels = model.preprocessor.preprocess(
        SpecStruct(image=frames), SpecStruct(action=actions), 'eval')
    return model.init_variables(jax.random.PRNGKey(0), features, labels,
                                'train')

  def test_tp_with_ring_rejected(self):
    mesh = parallel.create_mesh({'data': 4, 'model': 2})
    with pytest.raises(ValueError, match='ring'):
      self._init(mesh, tp_axis='model', attention_mode='ring')

  def test_tp_inside_pipeline_rejected(self):
    mesh = parallel.create_mesh({'data': 2, 'model': 2, 'pipe': 2})
    with pytest.raises(ValueError, match='tp_axis'):
      self._init(mesh, tp_axis='model', pipe_axis='pipe')

  def test_ring_inside_pipeline_rejected(self):
    mesh = parallel.create_mesh({'data': 4, 'pipe': 2})
    with pytest.raises(ValueError, match='ring'):
      self._init(mesh, attention_mode='ring', pipe_axis='pipe')

  def test_moe_inside_pipeline_rejected(self):
    mesh = parallel.create_mesh({'data': 2, 'expert': 2, 'pipe': 2})
    with pytest.raises(ValueError, match='MoE'):
      self._init(mesh, moe_experts=4, ep_axis='expert', pipe_axis='pipe')
