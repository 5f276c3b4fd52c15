"""Pallas flash attention numerics vs the XLA oracle.

Runs through the Pallas interpreter on the CPU test mesh; the compiled
TPU path shares the same kernel (what it measures in a cell: PERF.md
section 5).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers import transformer as transformer_lib
from tensor2robot_tpu.parallel.flash_attention import flash_attention
from tensor2robot_tpu.parallel.ring_attention import reference_attention


flash_lib = transformer_lib.flash_lib  # the module; the package exports the function


# The two backward paths, by the kernels each launches: ``_flash_bwd_pallas``
# chooses from the bytes dk and dv of a k/v head take.
BACKWARDS = {'fused': 1, 'two kernels': 2}


def _take(backward, monkeypatch):
  """Steers a call onto ``backward`` as a length past the budget would."""
  if backward == 'two kernels':
    monkeypatch.setattr(flash_lib, 'FUSED_BWD_RESIDENT_BYTES', 0)


def _qkv(b=2, l=256, h=4, d=64, dtype=np.float32, seed=0):
  rng = np.random.RandomState(seed)
  return tuple(rng.randn(b, l, h, d).astype(dtype) for _ in range(3))


class TestFlashAttention:

  @pytest.mark.parametrize('causal', [False, True])
  def test_matches_xla_oracle(self, causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  def test_uneven_q_k_block_sizes(self):
    q, k, v = _qkv(l=256)
    out = flash_attention(q, k, v, block_q=128, block_k=32)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  def test_bfloat16_inputs(self):
    q, k, v = _qkv(d=128)
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(qb, kb, vb)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2)

  def test_custom_scale(self):
    q, k, v = _qkv(l=128)
    out = flash_attention(q, k, v, scale=0.25, block_q=64, block_k=64)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  def test_indivisible_length_steps_blocks_down(self):
    """L that doesn't divide the requested blocks runs anyway (the kernel
    steps down to the largest dividing block) and matches the oracle."""
    q, k, v = _qkv(l=200)  # 200 % 128 != 0; largest dividing block is 8
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=1e-3)

  def test_differentiable(self):
    """The kernel composes with jax.grad (interpreter autodiff path)."""
    q, k, v = _qkv(b=1, l=64, h=2, d=32)

    def loss(q):
      return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

    def ref_loss(q):
      return jnp.sum(reference_attention(
          jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)) ** 2)

    g = jax.grad(loss)(jnp.asarray(q))
    g_ref = jax.grad(ref_loss)(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)

  @pytest.mark.parametrize('backward', list(BACKWARDS))
  @pytest.mark.parametrize('causal', [False, True])
  def test_full_gradients_match_oracle(self, causal, backward, monkeypatch):
    """dq, dk AND dv from the Pallas backward (parallel/flash_attention.py
    _flash_bwd_pallas: one fused kernel, or two where a k/v head's dk and
    dv outgrow the budget) against the XLA oracle. block_*_bwd=32 with
    L=128 makes the BACKWARD grids 4x4 blocks, so the cross-block
    accumulate / init / finalize logic and the causal skip actually run
    (the backward ignores the forward block sizes)."""
    _take(backward, monkeypatch)
    q, k, v = _qkv(b=1, l=128, h=2, d=32)

    def loss(fn):
      def f(q, k, v):
        out = fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        return jnp.sum(out * (1.0 + 0.01 * out))
      return f

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32,
        block_q_bwd=32, block_k_bwd=32))
    ref = loss(lambda q, k, v: reference_attention(q, k, v, causal=causal))
    grads = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for g, g_ref, name in zip(grads, grads_ref, 'qkv'):
      np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                 atol=2e-4, err_msg='d' + name)

  def test_misaligned_length_raises(self):
    """L % 8 != 0 raises the documented ValueError instead of reaching
    Mosaic with an unaligned full-length block."""
    q, k, v = _qkv(l=100)
    with pytest.raises(ValueError, match='multiple of 8'):
      flash_attention(q, k, v)


class TestRingWithPallas:

  def test_ring_attention_pallas_path_matches_oracle(self):
    """The carry-kernel ring path == single-device oracle on the CPU mesh."""
    from tensor2robot_tpu.parallel import create_mesh
    from tensor2robot_tpu.parallel.ring_attention import ring_self_attention

    mesh = create_mesh({'data': 8})
    q, k, v = _qkv(b=2, l=256, h=2, d=32, seed=4)
    for causal in (False, True):
      out = ring_self_attention(
          jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
          seq_axis='data', causal=causal, use_pallas=True)
      ref = reference_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), causal=causal)
      np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                 atol=2e-6)


KERNELS = ('flash_attention_fwd', 'flash_attention_bwd_dkv',
           'flash_attention_bwd_dq')


def _attention_losses():
  """Freshly built each call (a traced function is cached by identity):
  {caller: (loss, arguments)} through the bare kernel and through
  MultiHeadAttention with the flash backend."""
  q, k, v = (jnp.asarray(x) for x in _qkv(b=1, l=64, h=2, d=32, seed=7))
  layer = transformer_lib.MultiHeadAttention(
      num_heads=2, head_dim=32, attention_mode='flash')
  x = jnp.asarray(np.random.RandomState(8).randn(1, 64, 48), jnp.float32)
  params = layer.init(jax.random.PRNGKey(0), x)
  return {
      'flash_attention': (lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
          q, k, v, causal=True, block_q=32, block_k=16, block_q_bwd=16,
          block_k_bwd=32))), (q, k, v)),
      'MultiHeadAttention': (
          lambda params, x: jnp.sum(jnp.sin(layer.apply(params, x))),
          (params, x)),
  }


class TestNamedResiduals:
  """The names on the custom VJP's residuals are the identity for every
  caller that has no checkpoint policy asking for them."""

  def test_the_names_exported_are_the_ones_given(self, jaxpr_calls):
    loss, args = _attention_losses()['flash_attention']
    _, tags = jaxpr_calls(jax.grad(loss), *args)
    assert set(tags) == set(flash_lib.BACKWARD_READS) == {
        flash_lib.FLASH_Q, flash_lib.FLASH_K, flash_lib.FLASH_V,
        flash_lib.FLASH_OUT, flash_lib.FLASH_LSE}
    assert len(set(flash_lib.BACKWARD_READS)) == 5
    # The primal function is not the forward rule: no name outside a gradient.
    assert not jaxpr_calls(loss, *args)[1]

  @pytest.mark.parametrize('checkpointed', [False, True],
                           ids=['plain', 'policy-less checkpoint'])
  @pytest.mark.parametrize('caller', ['flash_attention',
                                      'MultiHeadAttention'])
  def test_without_a_policy_kernels_and_values_are_the_untagged_ones(
      self, caller, checkpointed, jaxpr_calls, monkeypatch):
    def grad():
      loss, args = _attention_losses()[caller]
      fn = jax.value_and_grad(
          jax.checkpoint(loss) if checkpointed else loss,
          argnums=tuple(range(len(args))))
      return fn(*args), *jaxpr_calls(fn, *args)

    got, calls, _ = grad()
    # A checkpoint with no policy runs the forward kernel again, as ever;
    # the backward is one kernel, under the dq kernel's name.
    assert [calls[k] for k in KERNELS] == [2 if checkpointed else 1, 0, 1]
    # With the tags made the identity the module is the one before them.
    monkeypatch.setattr(flash_lib, 'checkpoint_name', lambda x, name: x)
    want, untagged, tags = grad()
    assert not tags
    assert [untagged[k] for k in KERNELS] == [calls[k] for k in KERNELS]
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
      np.testing.assert_array_equal(g, w)

  def test_a_policy_that_asks_for_out_and_lse_drops_the_second_forward(
      self, jaxpr_calls):
    loss, args = _attention_losses()['flash_attention']
    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            flash_lib.FLASH_OUT, flash_lib.FLASH_LSE))
    calls, _ = jaxpr_calls(jax.grad(kept, argnums=(0, 1, 2)), *args)
    assert [calls[k] for k in KERNELS] == [1, 0, 1]
    for g, w in zip(jax.grad(kept, argnums=(0, 1, 2))(*args),
                    jax.grad(loss, argnums=(0, 1, 2))(*args)):
      np.testing.assert_array_equal(g, w)


class TestBlockDiffusionMask:
  """The third mask mode of the three kernels (tests/test_sdar.py holds it
  against the dense mask at every tile shape): here, that it is the same
  kernel family, that the other two modes are what they were, and what the
  gauges count."""

  def _qkv(self, length=32, kv_heads=2):
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 2 * length, 4, 16))
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (1, 2 * length, kv_heads, 16)) for i in (1, 2))
    return q, k, v

  @pytest.mark.parametrize('backward', list(BACKWARDS))
  def test_the_same_kernels_once_each(self, backward, jaxpr_calls,
                                      monkeypatch):
    _take(backward, monkeypatch)
    q, k, v = self._qkv()
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_diffusion=(32, 4), block_q=16, block_k=16) ** 2)
    calls, tags = jaxpr_calls(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    # The dk/dv kernel only of the two; the fused one carries dq's name.
    assert [calls[name] for name in KERNELS] == [
        1, BACKWARDS[backward] - 1, 1]
    assert set(tags) == set(flash_lib.BACKWARD_READS)

  def test_a_policy_keeps_the_residuals_of_a_masked_call(self, jaxpr_calls):
    q, k, v = self._qkv()
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_diffusion=(32, 4), block_q=16, block_k=16) ** 2)
    kept = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            *flash_lib.BACKWARD_READS))
    calls, _ = jaxpr_calls(jax.grad(kept, argnums=(0, 1, 2)), q, k, v)
    assert [calls[name] for name in KERNELS] == [1, 0, 1]
    for g, w in zip(jax.grad(kept, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss, argnums=(0, 1, 2))(q, k, v)):
      np.testing.assert_array_equal(g, w)

  @pytest.mark.parametrize('window', [None, 8])
  def test_causal_and_window_are_what_they_were(self, window):
    """Causal and window calls still match the dense oracle; and a mask of
    ONE block (B = L) is full attention within each half: block-causal
    with one block among the clean tokens, the own block among the noised
    ones, which see no clean token (none lies in an earlier block)."""
    q, k, v = self._qkv()
    out = flash_attention(q, k, v, causal=True, window=window, block_q=16,
                          block_k=16)
    want = transformer_lib.scaled_dot_attention(q, k, v, True, window)
    np.testing.assert_allclose(out, want, atol=2e-6)
    one_block = flash_attention(q, k, v, block_diffusion=(32, 32),
                                block_q=16, block_k=16)
    clean = flash_attention(q[:, 32:], k[:, 32:], v[:, 32:], block_q=16,
                            block_k=16)
    np.testing.assert_allclose(one_block[:, 32:], clean, atol=2e-6)
    noised = flash_attention(q[:, :32], k[:, :32], v[:, :32], block_q=16,
                             block_k=16)
    np.testing.assert_allclose(one_block[:, :32], noised, atol=2e-6)

  def test_blocks_of_one_make_the_clean_half_causal(self):
    q, k, v = self._qkv()
    masked = flash_attention(q, k, v, block_diffusion=(32, 1), block_q=16,
                             block_k=16)
    causal = flash_attention(q[:, 32:], k[:, 32:], v[:, 32:], causal=True,
                             block_q=16, block_k=16)
    np.testing.assert_allclose(masked[:, 32:], causal, atol=2e-6)

  @pytest.mark.parametrize('backward', list(BACKWARDS))
  @pytest.mark.parametrize('causal, window, diffusion, pairs, tiles', [
      (True, None, None, 64 * 65 // 2, 10),
      (True, 8, None, 8 * 9 // 2 + 56 * 8, 7),
      (False, None, (32, 4), 32 * 32 + 32 * 4, 2 + 3 + 3),
  ])
  def test_the_gauges_count_the_mask_and_the_tiles(self, causal, window,
                                                   diffusion, pairs, tiles,
                                                   backward, monkeypatch):
    """... and the grid steps: every kernel launches the tiles it computes
    and no other, so steps x tile = pairs computed, forward and backward;
    the backward gauges are ONE backward kernel's on either path."""
    from tensor2robot_tpu.observability import get_registry

    _take(backward, monkeypatch)
    assert flash_lib.mask_pairs(64, 64, causal, window, diffusion) == pairs
    assert flash_lib.tiles_computed(4, 4, 16, 16, causal, window,
                                    diffusion) == tiles
    tiles_bwd = flash_lib.tiles_computed(4, 2, 16, 32, causal, window,
                                         diffusion)
    q, k, v = self._qkv()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=causal, window=window, block_diffusion=diffusion,
        block_q=16, block_k=16, block_q_bwd=16, block_k_bwd=32))))(q)
    value = lambda name: get_registry().gauge('attention/' + name).value
    assert value('mask_pairs_needed') == 4 * pairs
    assert value('mask_pairs_computed') == 4 * tiles * 256
    assert value('grid_steps') == 4 * tiles
    assert value('mask_pairs_computed_bwd') == 4 * tiles_bwd * 512
    assert value('grid_steps_bwd') == 4 * tiles_bwd
    assert value('backward_kernels') == BACKWARDS[backward]
    # The grids themselves: (heads, steps). The fused backward is ONE
    # kernel under the dq kernel's name, on its grid; of the two, dk/dv's
    # runs over the 2 k/v heads, each k/v block followed by both query
    # heads of its group. (Each kernel sits in the jitted launcher of its
    # call.)
    def kernels(jaxpr):
      for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
          yield eqn.params['name'], eqn.params['grid_mapping'].grid
        for inner in jax.core.jaxprs_in_params(eqn.params):
          yield from kernels(inner)
    grids = list(kernels(jaxpr.jaxpr))
    two = [('flash_attention_bwd_dkv', (2, 2 * tiles_bwd))] * (
        backward == 'two kernels')
    assert sorted(grids) == sorted(
        [('flash_attention_fwd', (4, tiles)),
         ('flash_attention_bwd_dq', (4, tiles_bwd))] + two)

  def test_an_unmasked_call_sets_no_gauge(self):
    assert flash_lib.mask_pairs(64, 48, False, None, None) == 64 * 48
    assert flash_lib.tiles_computed(4, 3, 16, 16, False, None, None) == 12


def _dense_mask(l_q, l_k, causal, window, diffusion):
  """[l_q, l_k] bool: the mask as the kernels count it. Under ``causal``
  both sides count positions from their first (row i sees columns j <= i),
  also where the lengths differ."""
  if diffusion is not None:
    return np.asarray(flash_lib.block_diffusion_mask(*diffusion))
  q_pos, k_pos = np.arange(l_q)[:, None], np.arange(l_k)[None, :]
  mask = np.ones((l_q, l_k), bool)
  if causal:
    mask &= q_pos >= k_pos
  if window is not None:
    mask &= q_pos - k_pos < window
  return mask


# name: (l_q, l_k, causal, window, diffusion)
MASKS = {
    'causal': (256, 256, True, None, None),
    'window': (256, 256, True, 40, None),
    'block diffusion': (256, 256, False, None, (128, 4)),
    'causal, more keys than queries': (64, 256, True, None, None),
    'unmasked': (256, 192, False, None, None),
}
# name: (k_resident, group)
GRIDS = {'forward and dq': (False, 1), 'dk/dv': (True, 1),
         'dk/dv of 4 query heads': (True, 4)}


class TestTileTable:
  """``tile_table``: the grid of each kernel is the list of tiles its mask
  keeps, in the order that forms every sum as the rectangular sweep did."""

  @pytest.mark.parametrize('block_q, block_k', [(32, 32), (16, 64)])
  @pytest.mark.parametrize('grid', list(GRIDS))
  @pytest.mark.parametrize('mask', list(MASKS))
  def test_every_needed_tile_once_in_order_with_its_flags(self, mask, grid,
                                                          block_q, block_k):
    l_q, l_k, causal, window, diffusion = MASKS[mask]
    k_resident, group = GRIDS[grid]
    n_q, n_k = l_q // block_q, l_k // block_k
    head, qs, ks, flags = flash_lib.tile_table(
        n_q, n_k, block_q, block_k, causal, window, diffusion,
        k_resident=k_resident, group=group)
    # The oracle: a tile is needed when the dense mask keeps a pair of it,
    # on the mask's edge when it does not keep them all.
    tiles = _dense_mask(l_q, l_k, causal, window, diffusion).reshape(
        n_q, block_q, n_k, block_k)
    needed, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    want = {(h, i, j) for h in range(group) for i, j in np.argwhere(needed).tolist()}
    # An accumulator the mask leaves empty gets one step of its own.
    empty = np.flatnonzero(~needed.any(axis=0 if k_resident else 1))
    want |= {(0, 0, e) if k_resident else (0, e, 0) for e in empty.tolist()}
    steps = list(zip(head.tolist(), qs.tolist(), ks.tolist()))
    assert len(steps) == len(set(steps)) and set(steps) == want
    assert len(steps) == group * flash_lib.tiles_computed(
        n_q, n_k, block_q, block_k, causal, window, diffusion) + len(empty)
    assert bool(len(empty)) == (mask == 'causal, more keys than queries'
                                and k_resident)
    # Order: the accumulator's steps together, the streamed side ascending
    # (k within a q block; query head, then q block, within a k/v block).
    order = [(j, h, i) if k_resident else (i, j) for h, i, j in steps]
    assert order == sorted(order)
    resident = [o[0] for o in order]
    for t, flag in enumerate(flags.tolist()):
      first = t == 0 or resident[t - 1] != resident[t]
      last = t == len(steps) - 1 or resident[t + 1] != resident[t]
      edge = not whole[qs[t], ks[t]]
      assert flag == (flash_lib.FIRST * first + flash_lib.LAST * last +
                      flash_lib.EDGE * edge)

  @pytest.mark.parametrize(
      'n_q, n_k, block_q, block_k, causal, window, diffusion, steps', [
          # The third cell: 16,384 positions, forward and backward blocks.
          (16, 16, 1024, 1024, False, None, (8192, 4), 80),
          (32, 16, 512, 1024, False, None, (8192, 4), 160),
          # The second cell: a full layer and a window-4096 layer at 8,192.
          (8, 8, 1024, 1024, True, None, None, 36),
          (8, 8, 1024, 1024, True, 4096, None, 30),
          (16, 8, 512, 1024, True, None, None, 72),
          (16, 8, 512, 1024, True, 4096, None, 60),
      ])
  def test_the_cells_launch_what_they_compute(self, n_q, n_k, block_q,
                                              block_k, causal, window,
                                              diffusion, steps):
    mask = (n_q, n_k, block_q, block_k, causal, window, diffusion)
    assert flash_lib.tiles_computed(*mask) == steps
    assert flash_lib.tile_table(*mask).shape == (4, steps)
    assert flash_lib.tile_table(*mask, k_resident=True,
                                group=7).shape == (4, 7 * steps)

  def test_an_unmasked_call_launches_the_rectangle(self):
    head, qs, ks, _ = flash_lib.tile_table(3, 2, 16, 16, False, None, None)
    assert list(zip(qs.tolist(), ks.tolist())) == [
        (i, j) for i in range(3) for j in range(2)] and not head.any()


class TestMostlySkippedGrids:
  """Outputs and all three gradients against a dense oracle under the
  kernels' own mask, on both backward paths, at sizes where the mask drops
  most of the rectangle and a q block's k blocks are not neighbours; query
  heads in groups of 1, 2 and 3 (the fused backward sums dk and dv over a
  group's heads in accumulators that outlive a head)."""

  @pytest.mark.parametrize('backward', list(BACKWARDS))
  @pytest.mark.parametrize('l_q, l_k, causal, window, diffusion, heads', [
      (512, 512, False, None, (256, 4), (4, 2)),  # 24 of 64 tiles; q block 1: k 1, 4, 5
      (512, 512, True, 70, None, (4, 2)),         # 22 of 64
      (256, 256, True, None, None, (4, 2)),       # 10 of 16
      (128, 256, True, None, None, (4, 2)),       # k/v blocks 2 and 3: no tile at all
      (128, 192, False, None, None, (2, 2)),      # the whole rectangle, l_q != l_k
      (256, 256, True, None, None, (6, 2)),
      (256, 256, False, None, (128, 4), (3, 1)),
  ], ids=['block diffusion', 'window', 'causal',
          'causal, k/v blocks without a tile', 'unmasked, more keys',
          'causal, groups of 3', 'block diffusion, one group of 3'])
  def test_outputs_and_gradients_match_the_dense_mask(
      self, l_q, l_k, causal, window, diffusion, heads, backward,
      monkeypatch):
    _take(backward, monkeypatch)
    h, h_kv = heads
    key = jax.random.PRNGKey(l_q + l_k)
    q = jax.random.normal(key, (1, l_q, h, 16))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, l_k, h_kv, 16))
            for i in (1, 2))
    mask = jnp.asarray(_dense_mask(l_q, l_k, causal, window, diffusion))

    def dense(q, k, v):
      k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
      scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) / 4.0
      probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
      return jnp.einsum('bhqk,bkhd->bqhd', probs, v)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, block_diffusion=diffusion,
        block_q=64, block_k=64, block_q_bwd=64, block_k_bwd=64)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-6)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
      np.testing.assert_allclose(g, w, atol=1e-5, err_msg='d' + name)
    if l_k > l_q and causal:
      # the keys no query sees get a gradient of exactly zero
      assert not np.any(np.asarray(got[1])[:, l_q:]) and not np.any(
          np.asarray(got[2])[:, l_q:])


class TestAValueWidthUnlikeTheKeyWidth:
  """Latent attention's q and k of one width against v of another (192 and
  128 in the Xing4.0 cell), through the forward kernel and both backward
  paths, against the dense oracle; and the path of equal widths unchanged."""

  @pytest.mark.parametrize('backward', list(BACKWARDS))
  @pytest.mark.parametrize('kv_heads', [4, 2], ids=['mha', 'gqa'])
  def test_outputs_and_gradients_match_the_oracle(self, kv_heads, backward,
                                                  monkeypatch):
    _take(backward, monkeypatch)
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 128, 4, 48), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, kv_heads, 48), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, kv_heads, 16), jnp.float32)
    cotangent = jnp.asarray(rng.randn(1, 128, 4, 16), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.2, block_q=32, block_k=32,
        block_q_bwd=32, block_k_bwd=32)
    dense = lambda q, k, v: transformer_lib.scaled_dot_attention(
        q, k, v, True, scale=0.2)
    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    assert out.shape == (1, 128, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)
    for got, ref, name in zip(vjp(cotangent), want_vjp(cotangent), 'qkv'):
      assert got.shape == ref.shape
      np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                                 err_msg='d' + name)

  def test_the_equal_width_path_is_the_parents_to_the_bit(self):
    """Outputs and the three gradients of calls whose value width is the key
    width, digested: what the kernels gave before they took two widths (the
    digests were read from the parent commit's code, same inputs)."""
    import hashlib

    digests = {
        'f32_d32_causal': ((1, 128, 2, 2, 32), np.float32, True, None,
                           'af8554191128032a'),
        'bf16_d128_gqa_window': ((1, 128, 4, 2, 128), jnp.bfloat16, True, 64,
                                 'a3efc06aaf745c6c'),
        'f32_d64_full': ((1, 64, 2, 2, 64), np.float32, False, None,
                         '6c50dbd87d820857'),
    }
    rng = np.random.RandomState(11)
    for name, (shape, dtype, causal, window, want) in digests.items():
      b, l, h, h_kv, d = shape
      q, k, v = (jnp.asarray(rng.randn(b, l, heads, d), dtype)
                 for heads in (h, h_kv, h_kv))
      cotangent = jnp.asarray(rng.randn(b, l, h, d), dtype)
      out, vjp = jax.vjp(lambda q, k, v: flash_attention(
          q, k, v, causal=causal, window=window, block_q=32, block_k=32,
          block_q_bwd=32, block_k_bwd=32), q, k, v)
      digest = hashlib.sha256()
      for array in (out,) + vjp(cotangent):
        digest.update(np.asarray(array).tobytes())
      assert digest.hexdigest()[:16] == want, name

  def test_the_widths_the_kernels_run_at(self):
    assert flash_lib.kernel_width(64) == 128      # padded, as before
    assert flash_lib.kernel_width(128) == 128
    assert flash_lib.kernel_width(192) == 192     # whole, not 256
    # dk [l_k, 192] and dv [l_k, 128] of a head: f32 accumulators and bf16
    # output blocks twice, 8 bytes an element; equal widths count as before.
    assert flash_lib._fused_bwd_resident_bytes(4096, 192, jnp.bfloat16,
                                               128) == 4096 * (192 + 128) * 8
    assert flash_lib._fused_bwd_resident_bytes(8192, 128, jnp.bfloat16) == \
        flash_lib._fused_bwd_resident_bytes(8192, 128, jnp.bfloat16, 128) == \
        2 * 8192 * 128 * 8

  def test_q_and_k_of_two_widths_are_refused(self):
    q, k, v = _qkv(l=64, d=32)
    with pytest.raises(ValueError, match='one head width'):
      flash_attention(q, k[..., :16], v)


def _brute_spans(dense, table, block_q, block_k, strip):
  """``strip_spans`` from the dense mask: for each EDGE step and each strip
  of ``strip`` rows, (first, count) of the ``strip``-column sub-blocks from
  the first that holds a kept pair to the last; (0, 0) elsewhere."""
  _, qs, ks, flags = table
  spans = np.zeros((len(qs), block_q // strip, 2), int)
  for t in np.flatnonzero(flags & flash_lib.EDGE):
    for r in range(block_q // strip):
      row = qs[t] * block_q + r * strip
      held = [j for j in range(block_k // strip) if dense[
          row:row + strip,
          ks[t] * block_k + j * strip:ks[t] * block_k + (j + 1) * strip].any()]
      if held:
        spans[t, r] = held[0], held[-1] - held[0] + 1
  return spans


# name: (l, causal, window, diffusion)
EDGE_MASKS = {
    'causal': (256, True, None, None),
    'window': (256, True, 40, None),
    'block diffusion': (256, False, None, (128, 4)),
    'block diffusion, tiles across the halves': (384, False, None, (192, 4)),
    'block diffusion, blocks of 24': (384, False, None, (192, 24)),
    'unmasked': (256, False, None, None),
}


class TestEdgeTilesRunAsStrips:
  """A tile the mask keeps only in part (EDGE) runs as strips of STRIP_ROWS
  query rows, each over the sub-blocks that hold its pairs; a tile the mask
  keeps whole runs with no mask. The host side against the dense mask, then
  the kernels against the dense oracle at blocks that engage strips."""

  @pytest.mark.parametrize('strip, block_q, block_k', [
      (16, 64, 64), (32, 64, 128), (16, 32, 64)])
  @pytest.mark.parametrize('mask', list(EDGE_MASKS))
  def test_each_strips_span_holds_its_pairs(self, mask, strip, block_q,
                                            block_k, monkeypatch):
    monkeypatch.setattr(flash_lib, 'STRIP_ROWS', strip)
    l, causal, window, diffusion = EDGE_MASKS[mask]
    dense = _dense_mask(l, l, causal, window, diffusion)
    table = flash_lib.tile_table(l // block_q, l // block_k, block_q,
                                 block_k, causal, window, diffusion)
    spans = flash_lib.strip_spans(table, l, l, block_q, block_k, causal,
                                  window, diffusion)
    edge = table[3] & flash_lib.EDGE != 0
    assert edge.any() == (mask != 'unmasked')
    # A call with no edge step runs no strip.
    want = _brute_spans(dense, table, block_q, block_k, strip)
    np.testing.assert_array_equal(spans, want if edge.any() else want[:, :0])
    # Pairs computed: a whole tile at every interior step, the spans' sub-
    # blocks at an edge step; never fewer than the mask keeps.
    computed = flash_lib.pairs_computed(table, spans, block_q, block_k)
    assert computed == (np.count_nonzero(~edge) * block_q * block_k +
                        strip * strip * spans[..., 1].sum())
    assert dense.sum() <= computed <= len(edge) * block_q * block_k
    assert computed < len(edge) * block_q * block_k or mask == 'unmasked'

  def test_blocks_under_two_strips_run_edge_tiles_whole(self, monkeypatch):
    monkeypatch.setattr(flash_lib, 'STRIP_ROWS', 32)
    for block_q, block_k in ((32, 64), (64, 48), (48, 64)):
      table = flash_lib.tile_table(192 // block_q, 192 // block_k, block_q,
                                   block_k, True, None, None)
      spans = flash_lib.strip_spans(table, 192, 192, block_q, block_k, True,
                                    None, None)
      assert spans.shape == (table.shape[1], 0, 2)
      assert flash_lib.pairs_computed(table, spans, block_q, block_k) == \
          table.shape[1] * block_q * block_k

  @pytest.mark.parametrize(
      'l, block_q, block_k, causal, window, diffusion, strip, edge, ratio', [
          # The third cell: forward (1024, 1024) and backward (512, 1024).
          (16384, 1024, 1024, False, None, (8192, 4), 256, 24, 1.0620),
          (16384, 512, 1024, False, None, (8192, 4), 256, 48, 1.0620),
          (16384, 1024, 1024, False, None, (8192, 4), 128, 24, 1.0307),
          # A full layer of the second, fourth and sixth cells; the second
          # cell's window layers; the fifth cell's forward at 4,096.
          (8192, 1024, 1024, True, None, None, 256, 8, 1.0311),
          (8192, 1024, 1024, True, 4096, None, 256, 12, 1.0624),
          (4096, 1024, 1024, True, None, None, 256, 4, 1.0622),
      ])
  def test_at_the_cells_shapes(self, l, block_q, block_k, causal, window,
                               diffusion, strip, edge, ratio, monkeypatch):
    """The edge steps a head and the pairs computed over the pairs the mask
    keeps, as the gauges read them; where the parent computed edge tiles
    whole: 1.2494, 1.1249, 1.2499 and 1.2497."""
    monkeypatch.setattr(flash_lib, 'STRIP_ROWS', strip)
    table = flash_lib.tile_table(l // block_q, l // block_k, block_q,
                                 block_k, causal, window, diffusion)
    spans = flash_lib.strip_spans(table, l, l, block_q, block_k, causal,
                                  window, diffusion)
    assert np.count_nonzero(table[3] & flash_lib.EDGE) == edge
    computed = flash_lib.pairs_computed(table, spans, block_q, block_k)
    needed = flash_lib.mask_pairs(l, l, causal, window, diffusion)
    assert computed / needed == pytest.approx(ratio, abs=5e-5)

  @pytest.mark.parametrize('mask, d_v, blocks', [
      ('causal', 16, (128, 128, 64, 128)),
      ('window', 16, (128, 128, 128, 64)),
      ('causal', 8, (128, 64, 128, 128)),
      ('block diffusion', 16, (128, 128, 64, 128)),
  ], ids=['causal', 'window', 'value width unlike the key width',
          'block diffusion'])
  def test_outputs_gradients_and_gauges_against_the_dense_mask(
      self, mask, d_v, blocks, monkeypatch):
    """Four strips of 32 rows a 128-row block (two of a 64-row one), query
    heads in groups of 2; the gauges count each kernel's strips' spans."""
    from tensor2robot_tpu.observability import get_registry

    monkeypatch.setattr(flash_lib, 'STRIP_ROWS', 32)
    l, causal, window, diffusion = EDGE_MASKS[mask]
    block_q, block_k, block_q_bwd, block_k_bwd = blocks
    key = jax.random.PRNGKey(l + d_v)
    q = jax.random.normal(key, (1, l, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, l, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, l, 2, d_v))
    dense_mask = _dense_mask(l, l, causal, window, diffusion)

    def dense(q, k, v):
      k, v = (jnp.repeat(x, 2, axis=2) for x in (k, v))
      scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) / 4.0
      probs = jax.nn.softmax(jnp.where(dense_mask, scores, -jnp.inf), -1)
      return jnp.einsum('bhqk,bkhd->bqhd', probs, v)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, block_diffusion=diffusion,
        block_q=block_q, block_k=block_k, block_q_bwd=block_q_bwd,
        block_k_bwd=block_k_bwd)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-6)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
      np.testing.assert_allclose(g, w, atol=1e-5, err_msg='d' + name)
    value = lambda name: get_registry().gauge('attention/' + name).value
    for suffix, bq, bk in (('', block_q, block_k),
                           ('_bwd', block_q_bwd, block_k_bwd)):
      table = flash_lib.tile_table(l // bq, l // bk, bq, bk, causal, window,
                                   diffusion)
      spans = _brute_spans(dense_mask, table, bq, bk, 32)
      edge = np.count_nonzero(table[3] & flash_lib.EDGE)
      assert edge and value('edge_steps' + suffix) == 4 * edge
      assert value('mask_pairs_computed' + suffix) == 4 * (
          (table.shape[1] - edge) * bq * bk + 32 * 32 * spans[..., 1].sum())
