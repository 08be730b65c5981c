"""The KV layouts behind the continuous engine (dl/kv_layout.py).

Every case runs on both layouts: what the engine may assume of a layout
is what both give. References are independent of the code under test — a
page count kept by hand, numpy slices, the family forward against a plain
dense cache. The last class guards the benchmark's cells: they run
``DenseKV``, whose programs must stay the ones their compile caches hold.
"""

import dataclasses
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import kv_layout
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.models import llama
from modelx_tpu.parallel.mesh import make_mesh

SLOTS, MAX_LEN, PAGE = 4, 64, 16
LAYOUTS = ["dense", "paged"]


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def build(model, layout, stats=None, mesh="dp=1", live_tokens=0):
    cfg = model[0]
    server = types.SimpleNamespace(
        mesh=make_mesh(mesh, jax.devices()[: 4 if "tp" in mesh else 1]),
        family=FAMILIES["llama"], cfg=cfg)
    fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
    return kv_layout.build(
        server, fwd, init_cache, {} if stats is None else stats,
        max_slots=SLOTS, max_len=MAX_LEN, chunk_size=4,
        page_size=PAGE if layout == "paged" else 0,
        max_live_tokens=live_tokens, paged_attention="gather")


def scratch(kv, seed, length, rows=1):
    """A scratch cache of random values (what a prefill would leave)."""
    leaves, tree = jax.tree_util.tree_flatten(kv.init_cache(rows, length))
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_unflatten(
        tree, [jnp.asarray(rng.standard_normal(x.shape).astype(x.dtype)) for x in leaves])


def assert_trees_equal(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("layout", LAYOUTS)
class TestReservations:
    def test_nothing_leaks_and_only_a_short_pool_refuses(self, model, layout):
        """100 seeded admissions, growths and retirements against a page
        count kept by hand: a dense reservation never fails, a paged one
        fails exactly when the pool is short, and all comes back."""
        stats = {}
        kv = build(model, layout, stats, live_tokens=6 * PAGE)
        pages_free = 6 if layout == "paged" else 10**9
        held: dict[int, int] = {}  # slot -> pages it holds
        rng = np.random.RandomState(31)
        refused = 0
        for _ in range(100):
            slot = int(rng.randint(SLOTS))
            if slot in held and rng.rand() < 0.5:
                kv.release(slot)
                pages_free += held.pop(slot)
                continue
            tokens = int(rng.randint(1, MAX_LEN + 1))
            grow = max(0, -(-tokens // PAGE) - held.get(slot, 0))
            if slot not in held:
                assert kv.fits(tokens) == (grow <= pages_free)
            ok = kv.reserve(slot, tokens)
            assert ok == (grow <= pages_free), (slot, tokens, held, pages_free)
            if ok:
                held[slot] = held.get(slot, 0) + grow
                pages_free -= grow
            refused += not ok
            if layout == "paged":
                assert stats["pages_free"] == pages_free
        assert refused == 0 if layout == "dense" else refused > 0
        for slot in list(held):
            kv.release(slot)
        assert kv.fits(MAX_LEN) and kv.reserve(0, MAX_LEN)
        kv.release(0)
        if layout == "paged":
            assert stats["pages_free"] == stats["pages_total"] == 6
            assert not kv._row_pages and not kv._table.any()
            assert sorted(kv._free_pages) == list(range(1, 7))  # never the trash page

    def test_a_row_no_pool_could_hold_is_refused_by_name(self, model, layout):
        kv = build(model, layout, live_tokens=2 * PAGE)
        assert kv.never_holds(2 * PAGE) == ""
        assert ("pages" in kv.never_holds(2 * PAGE + 1)) == (layout == "paged")

    def test_reset_empties_every_slot(self, model, layout):
        stats = {}
        kv = build(model, layout, stats, live_tokens=4 * PAGE)
        assert kv.reserve(1, 3 * PAGE)
        kv.reset()
        assert kv.reserve(2, 4 * PAGE)
        if layout == "paged":
            assert stats["pages_free"] == 0 and list(kv._row_pages) == [2]


@pytest.mark.parametrize("layout", LAYOUTS)
class TestRoundTrips:
    def test_put_then_view_is_bit_for_bit(self, model, layout):
        """Write a scratch cache to a slot, snap it back; a second slot's
        rows are not touched by it."""
        kv = build(model, layout)
        state = kv.new_state()
        a, b = scratch(kv, 1, 32), scratch(kv, 2, 24)
        assert kv.reserve(2, 32) and kv.reserve(0, 24)
        state = kv.put(state, a, kv.at(2))
        state = kv.put(state, b, kv.at(0))
        assert_trees_equal(kv.view(state, kv.at(2), 32), a)
        assert_trees_equal(kv.view(state, kv.at(0), 24), b)
        assert_trees_equal(kv.view(state, kv.at(2), 16),
                           jax.tree_util.tree_map(lambda x: x[:, :16], a))

    def test_put_many_writes_each_row_to_its_slot_and_drops_pad_rows(self, model, layout):
        kv = build(model, layout)
        state = kv.new_state()
        burst = scratch(kv, 3, 32, rows=4)
        slots = np.array([3, 1, SLOTS, SLOTS], np.int32)  # two real rows, two pads
        assert kv.reserve(3, 32) and kv.reserve(1, 32)
        where = kv.at_many(slots)
        state = kv.put_many(state, burst, where)
        np.testing.assert_array_equal(np.asarray(kv.slot_of(where)), slots)
        for row, slot in ((0, 3), (1, 1)):
            assert_trees_equal(kv.view(state, kv.at(slot), 32),
                               jax.tree_util.tree_map(lambda x: x[row: row + 1], burst))
        assert kv.reserve(0, 32)  # a slot the burst did not name is still zeros
        for leaf in jax.tree_util.tree_leaves(kv.view(state, kv.at(0), 32)):
            assert not np.asarray(leaf).any()

    @pytest.mark.parametrize("filled", [16, 24])  # page-aligned, and not
    def test_a_piece_lands_in_the_rows_it_wrote(self, model, layout, filled):
        kv = build(model, layout)
        state = kv.new_state()
        head, piece = scratch(kv, 4, filled), scratch(kv, 5, 16)
        assert kv.reserve(1, filled)
        state = kv.put(state, head, kv.at(1))
        assert kv.reserve(1, filled + 16)
        where = kv.at(1, filled, 16)
        row = kv.view(state, where, MAX_LEN)
        assert_trees_equal(jax.tree_util.tree_map(lambda x: x[:, :filled], row), head)
        row = jax.tree_util.tree_map(
            lambda r, p: r.at[:, filled: filled + 16].set(p), row, piece)
        state = kv.put_piece(state, row, where)
        want = jax.tree_util.tree_map(
            lambda h, p: jnp.concatenate([h, p], axis=1), head, piece)
        assert_trees_equal(kv.view(state, kv.at(1), filled + 16), want)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_cached_step_equals_the_family_forward_on_a_dense_cache(model, layout):
    """Two live slots at different depths, two idle: logits of the live
    rows and the k/v they wrote equal a plain [slots, max_len] cache's."""
    cfg, params = model
    kv = build(model, layout)
    fwd, init_cache = kv.fwd, kv.init_cache
    state, ref = kv.new_state(), init_cache(SLOTS, MAX_LEN)
    offsets = np.zeros(SLOTS, np.int32)
    for slot, n in ((0, 5), (2, 19)):
        prompt = np.zeros((1, 32), np.int32)
        prompt[0, :n] = np.arange(1, n + 1) % 60 + 1
        _, small = fwd(params, jnp.asarray(prompt), kv_cache=init_cache(1, 32), cache_offset=0)
        assert kv.reserve(slot, 48)
        state = kv.put(state, small, kv.at(slot))
        ref = jax.tree_util.tree_map(lambda big, s: big.at[slot, :32].set(s[0]), ref, small)
        offsets[slot] = n
    tok = jnp.asarray([[7], [0], [9], [0]], jnp.int32)
    for _ in range(2):  # the second step reads what the first wrote
        logits, state = kv.step(params, tok, state, jnp.asarray(offsets), *kv.all_slots())
        want, ref = fwd(params, tok, kv_cache=ref, cache_offset=jnp.asarray(offsets))
        np.testing.assert_array_equal(np.asarray(logits)[[0, 2]], np.asarray(want)[[0, 2]])
        offsets[[0, 2]] += 1
    for slot in (0, 2):
        assert_trees_equal(
            kv.view(state, kv.at(slot), 32),
            jax.tree_util.tree_map(lambda big: big[slot: slot + 1, :32], ref))


@pytest.mark.parametrize("mesh", ["dp=1", "dp=2,tp=2"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_abstract_state_describes_the_allocated_one(model, layout, mesh):
    """What ``chunk_warmer`` lowers against is what ``allocate_device_state``
    makes: shapes and dtypes always, the placement where there is a mesh."""
    kv = build(model, layout, mesh=mesh)
    described, allocated = kv.abstract_state(), kv.new_state()
    assert described == jax.tree_util.tree_map(
        lambda x, d: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=d.sharding),
        allocated, described)
    assert jax.tree_util.tree_structure(described) == jax.tree_util.tree_structure(
        jax.eval_shape(kv.new_state))
    if kv.mesh.size > 1:
        for d, x in zip(jax.tree_util.tree_leaves(described),
                        jax.tree_util.tree_leaves(allocated), strict=True):
            assert d.sharding.is_equivalent_to(x.sharding, x.ndim)
    want_rows = 1 + -(-(MAX_LEN + 4 + PAGE) // PAGE) if layout == "paged" else SLOTS
    assert {x.shape[0] for x in jax.tree_util.tree_leaves(described)} == {want_rows}


# -- the cells' programs -------------------------------------------------------


@pytest.fixture(scope="module")
def server(model, tmp_path_factory):
    d = tmp_path_factory.mktemp("kv_layout")
    st.write_safetensors(str(d / "model.safetensors"),
                         {k: np.asarray(v) for k, v in model[1].items()})
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv


def walk(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (pjit, scan,
    while, cond) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from walk(sub)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_dense_programs_take_no_table_and_gather_nothing_from_the_cache(server, layout):
    """``_chunk_impl`` and ``_admit_nosmall`` keep their names, and on the
    dense layout take exactly the arguments they took before there was a
    layout and never index the cache through a table; the paged layout —
    the proof that the walk sees such a thing — adds the table and the
    gathers."""
    cb = ContinuousBatcher(server, max_slots=SLOTS, chunk_size=4,
                           page_size=PAGE if layout == "paged" else 0)
    try:
        state = (cb.server.params, cb._cache, cb._tok)
        n_state = len(jax.tree_util.tree_leaves(state))
        kv_shapes = {x.shape for x in jax.tree_util.tree_leaves(cb._cache)}
        prompt = jnp.zeros((1, 16), jnp.int32)
        one = lambda v, dt: jnp.asarray([v], dt)  # noqa: E731
        programs = {
            # offsets, steps, temp, seeds (no filters: top_k / top_p are None)
            "_chunk_impl": (jax.make_jaxpr(
                lambda *a: cb._chunk_prog.jit(*a, n_steps=4))(*state, *cb._chunk_args(False)), 4),
            # prompt, row_len, slot, temp, seed, first_step
            "_admit_nosmall": (jax.make_jaxpr(cb._admit_prog.jit)(
                cb.server.params, prompt, cb._cache, cb._tok, one(5, jnp.int32),
                cb.kv.at(1), one(0.0, jnp.float32), None, None,
                one(0, jnp.int32), one(0, jnp.int32)), 6),
        }
        for name, (jaxpr, n_inputs) in programs.items():
            (call,) = jaxpr.eqns  # the jitted program, under its name
            # the chunk program's name also says its static depth (one chunk here)
            assert call.params["name"] == name + ("_d1" if name == "_chunk_impl" else "")
            extra = len(jaxpr.jaxpr.invars) - n_state - n_inputs
            from_cache = [e for e in walk(jaxpr.jaxpr) if e.primitive.name == "gather"
                          and e.invars[0].aval.shape in kv_shapes]
            if layout == "dense":
                assert extra == 0 and not from_cache, (name, extra, from_cache)
            else:
                assert extra == 1  # the table, or the slot's row of it
                assert (name == "_chunk_impl") == bool(from_cache)
    finally:
        cb.close()


# -- a cache per layer kind ----------------------------------------------------


class TestLayerKinds:
    """``LayerKindKV`` behind the same seam: full layers whole, window layers
    as rings, counted by kind. References are numpy and counts kept by hand."""

    WINDOW, RING = 16, 32

    @pytest.fixture(scope="class")
    def kv(self):
        from modelx_tpu.models import laguna

        cfg = laguna.LagunaConfig.tiny(vocab_size=64)
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["laguna"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        self.stats = {}
        return kv_layout.build(server, fwd, init_cache, self.stats, max_slots=SLOTS,
                               max_len=MAX_LEN, chunk_size=4, page_size=0, max_live_tokens=0,
                               paged_attention="gather"), cfg

    def test_each_kind_gets_its_own_length_and_bytes_are_counted_by_kind(self, kv):
        kv, cfg = kv
        state = kv.new_state()
        lengths = {name: state[name].shape[1] for name, kind in kv.kinds.items() if kind != "counter"}
        assert lengths == {f"{x}{i}": self.RING if 1 <= i <= 3 else MAX_LEN
                           for i in range(5) for x in "kv"}
        leaf = SLOTS * cfg.num_kv_heads * cfg.head_dim * 4
        assert kv.stats["kv"]["bytes_full"] == 4 * MAX_LEN * leaf       # k and v of layers 0, 4
        assert kv.stats["kv"]["bytes_window"] == 6 * self.RING * leaf   # k and v of layers 1-3
        assert kv.stats["kv"]["window_positions"] == self.RING == self.WINDOW + 16
        assert kv.describe()[:3] == ("LayerKindKV", SLOTS, MAX_LEN)
        assert state["moe_counts"].shape == (4,) and kv.sharding((4,)) is None

    def test_fits_and_reserve_count_both_kinds(self, kv):
        kv, _ = kv
        kv.reset()
        held = {}
        rng = np.random.RandomState(5)
        for _ in range(60):
            slot = int(rng.randint(SLOTS))
            if slot in held and rng.rand() < 0.4:
                kv.release(slot)
                del held[slot]
            else:
                tokens = int(rng.randint(1, MAX_LEN + 20))
                assert kv.fits(tokens) == (tokens <= MAX_LEN)
                assert kv.reserve(slot, tokens) == (tokens <= MAX_LEN)
                if tokens <= MAX_LEN:
                    held[slot] = max(tokens, held.get(slot, 0))
            assert kv.stats["kv"]["positions_full"] == sum(held.values())
            assert kv.stats["kv"]["positions_window"] == sum(min(t, self.RING) for t in held.values())
        kv.reset()
        assert kv.stats["kv"]["positions_full"] == kv.stats["kv"]["positions_window"] == 0
        assert kv.never_holds(MAX_LEN) == ""

    @pytest.mark.parametrize("length", [16, 32, 48, 64])
    def test_a_scratch_lands_whole_on_full_layers_and_by_ring_index_on_window_layers(self, kv, length):
        """Position p of a scratch is at index p of a full leaf and at index
        p mod ring of a window leaf, which keeps the last ``ring`` positions."""
        kv, _ = kv
        small = scratch(kv, length, length)
        state = jax.jit(kv.put)(kv.new_state(), small, kv.at(2))
        for name, kind in kv.kinds.items():
            if kind == "counter":
                continue
            got, want = np.asarray(state[name]), np.asarray(small[name])[0]
            assert not got[[0, 1, 3]].any()  # the other slots stay as they were
            if kind == "full":
                np.testing.assert_array_equal(got[2, :length], want)
            else:
                for p in range(max(0, length - self.RING), length):
                    np.testing.assert_array_equal(got[2, p % self.RING], want[p])

    def test_put_many_lands_each_row_in_its_slot_and_drops_pad_rows(self, kv):
        kv, _ = kv
        small = scratch(kv, 7, 48, rows=4)
        where = kv.at_many(np.asarray([3, 0, SLOTS, SLOTS]))  # two real rows, two pad rows
        state = jax.jit(kv.put_many)(kv.new_state(), small, where)
        one = jax.jit(kv.put)
        want = kv.new_state()
        for row, slot in ((0, 3), (1, 0)):
            want = one(want, jax.tree_util.tree_map(lambda x: x[row:row + 1], small), kv.at(slot))
        assert_trees_equal(state, want)

    def test_counters_ride_home_below_the_slots_rows_and_wrap_at_32_bits(self, kv):
        kv, _ = kv
        state = dict(kv.new_state(), moe_counts=jnp.asarray([7, 5, 3, 16], jnp.int32))
        block = jnp.arange(SLOTS * 5, dtype=jnp.int32).reshape(SLOTS, 5)
        out = np.asarray(kv.ride(state, block))
        assert out.shape == (SLOTS + 4, 5)
        np.testing.assert_array_equal(out[:SLOTS], np.asarray(block))
        kv._last.clear()
        kv.stats["moe"].update(assignments=0, assignments_held=0, experts_hit=0, experts_read=0)
        kv.landed(out)
        assert [kv.stats["moe"][k] for k in ("assignments", "assignments_held", "experts_hit",
                                             "experts_read")] == [7, 5, 3, 16]
        wrapped = out.copy()
        wrapped[SLOTS:, 0] = np.asarray([2**31 - 1, 5, 4, 32], np.int64).astype(np.int32)
        kv.landed(wrapped)
        wrapped[SLOTS, 0] = np.int32(-(2**31) + 9)  # the device's int32 passed 2**31: +10
        kv.landed(wrapped)
        assert kv.stats["moe"]["assignments"] == 2**31 - 1 + 10
        assert (kv.stats["moe"]["assignments_held"], kv.stats["moe"]["experts_hit"]) == (5, 4)
        assert kv.stats["moe"]["experts_read"] == 32
        DenseKV = kv_layout.DenseKV
        assert DenseKV.ride(kv, state, block) is block  # the other layouts add nothing

    def test_the_abstract_state_describes_the_allocated_one(self, kv):
        kv, _ = kv
        described, allocated = kv.abstract_state(), kv.new_state()
        assert jax.tree_util.tree_structure(described) == jax.tree_util.tree_structure(allocated)
        for d, x in zip(jax.tree_util.tree_leaves(described), jax.tree_util.tree_leaves(allocated),
                        strict=True):
            assert (d.shape, d.dtype) == (x.shape, x.dtype)

    def test_a_dense_view_of_a_slot_is_refused(self, kv):
        kv, _ = kv
        with pytest.raises(kv_layout.Refused, match="no dense view"):
            kv.view(kv.new_state(), kv.at(0), 16)
        with pytest.raises(kv_layout.Refused, match="--kv-page-size.*--speculative-k"):
            kv_layout.LayerKindKV.refuse("laguna", page_size=16, prefix_cache=None,
                                         prefill_chunk=0, speculative_k=2)

    @pytest.mark.parametrize("asked,what", [
        ({"page_size": 16}, "--kv-page-size.*a ring is not paged"),
        ({"prefix_cache": object()}, "--prefix-cache.*a ring cannot give back a prefix"),
        ({"speculative_k": 2}, "--speculative-k.*several ring positions"),
    ])
    def test_a_ring_still_refuses_pages_prefix_cache_and_speculation_by_name(self, asked, what):
        base = dict(page_size=0, prefix_cache=None, prefill_chunk=16, speculative_k=0)
        with pytest.raises(kv_layout.Refused, match=what):
            kv_layout.LayerKindKV.refuse("laguna", ("full", "window"), **{**base, **asked})
        kv_layout.LayerKindKV.refuse("laguna", ("full", "window"), **base)  # chunked prefill: carried

    @pytest.mark.parametrize("filled", [0, 16, 48, 80])
    def test_a_piece_sees_a_ring_unrolled_and_its_last_positions_roll_back_in(self, kv, filled):
        """``view`` hands a window leaf over as the slot's last ``ring``
        positions in position order from ``filled - ring`` on, a full leaf's
        front as it is; ``put_piece`` takes the last ``ring`` positions up to
        the piece's end and puts position p at ``p mod ring``."""
        kv, cfg = kv
        ring, piece = self.RING, 16
        state = kv.new_state()
        position = lambda leaf: jnp.broadcast_to(  # each ring index holds its position's number
            (filled - 1 - (filled - 1 - jnp.arange(ring)) % ring).astype(leaf.dtype)[
                None, :, None, None], leaf.shape)
        state = {name: position(leaf) if kv.kinds[name] == "window" else leaf
                 for name, leaf in state.items()}
        where = kv.at(2, filled, piece)
        assert kv.slot_of(where) == 2 and kv.stats["kv_ring_pieces"] >= 1
        row = jax.jit(lambda c, w: kv.view(c, w, MAX_LEN))(state, where)
        assert int(row["ring_start"]) == filled - ring
        assert row["k0"].shape[:2] == (1, MAX_LEN) and row["k1"].shape[:2] == (1, ring)
        held = np.asarray(row["k1"][0, :, 0, 0])
        want = filled - ring + np.arange(ring)
        np.testing.assert_array_equal(held[want >= 0], want[want >= 0])
        # the forward hands back the last ``ring`` positions up to the piece's end
        end = filled + piece
        back = {name: jnp.broadcast_to((end - ring + jnp.arange(ring)).astype(leaf.dtype)[
            None, :, None, None], leaf.shape) if kv.kinds[name] == "window" else leaf
            for name, leaf in row.items() if name != "ring_start"}
        out = jax.jit(kv.put_piece)(state, back, where)
        ringed = np.asarray(out["k1"][2, :, 0, 0])
        for p in range(max(0, end - ring), end):
            assert ringed[p % ring] == p
        np.testing.assert_array_equal(out["k1"][1], state["k1"][1])  # the other slots' stand


class TestStateAndIndexLeaves:
    """``LayerKindKV`` over a family with states and an index and no ring
    (minicpm_sala): a state has no position axis, an index one row per n
    positions; a slot's leaves go to a prefill piece and come back."""

    @pytest.fixture(scope="class")
    def kv(self):
        from modelx_tpu.models import minicpm_sala

        cfg = minicpm_sala.SalaConfig.tiny(vocab_size=64)
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["minicpm_sala"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        return kv_layout.build(server, fwd, init_cache, {}, max_slots=SLOTS, max_len=MAX_LEN,
                               chunk_size=4, page_size=0, max_live_tokens=0,
                               paged_attention="gather", prefill_chunk=16), cfg

    def test_each_kind_gets_its_own_shape_and_bytes_are_counted_by_kind(self, kv):
        kv, cfg = kv
        state = kv.new_state()
        assert kv.kinds == {"k0": "full", "v0": "full", "c0": "index", "s1": "state", "s2": "state",
                            "sparse_counts": "counter"}
        assert state["k0"].shape == (SLOTS, MAX_LEN, 2 * 8) and state["c0"].shape == (SLOTS, MAX_LEN // 2, 2, 8)
        assert state["s1"].shape == (SLOTS, 4, 8, 8) and state["s1"].dtype == jnp.float32
        stats = kv.stats["kv"]
        assert stats["bytes_full"] == 2 * SLOTS * MAX_LEN * 16 * 4
        assert stats["bytes_index"] == SLOTS * MAX_LEN // 2 * 16 * 4
        assert stats["bytes_state"] == 2 * SLOTS * 4 * 8 * 8 * 4 and stats["bytes_window"] == 0
        assert kv.has_state and kv.describe()[-1] == tuple(sorted(kv.kinds.items()))

    def test_the_store_tells_this_layout_from_lagunas(self, kv):
        from modelx_tpu.models import laguna

        cfg = laguna.LagunaConfig.tiny(vocab_size=64)
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["laguna"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        other = kv_layout.build(server, fwd, init_cache, {}, max_slots=SLOTS, max_len=MAX_LEN,
                                chunk_size=4, page_size=0, max_live_tokens=0,
                                paged_attention="gather")
        assert other.describe() != kv[0].describe() and not other.has_state

    @pytest.mark.parametrize("length", [16, 48])
    def test_an_admissions_scratch_lands_whole_state_and_all(self, kv, length):
        kv, _ = kv
        small = scratch(kv, length, length)
        state = jax.jit(kv.put)(kv.new_state(), small, kv.at(2))
        for name, kind in kv.kinds.items():
            if kind == "counter":
                continue
            got, want = np.asarray(state[name]), np.asarray(small[name])[0]
            assert not got[[0, 1, 3]].any()  # the other slots stay as they were
            if kind == "state":
                np.testing.assert_array_equal(got[2], want)
            else:
                np.testing.assert_array_equal(got[2, : want.shape[0]], want)
                assert want.shape[0] == (length // 2 if kind == "index" else length)

    def test_a_piece_is_handed_the_slots_leaves_and_gives_them_back(self, kv):
        """``view`` of a slot: the front of its keys and values, as many index
        rows as cover them, its state whole, no counter; ``put_piece`` writes
        them back where they were, and no other slot moves."""
        kv, _ = kv
        rng = np.random.RandomState(3)
        state = {n: (x if kv.kinds[n] == "counter" else
                     jnp.asarray(rng.standard_normal(x.shape).astype(x.dtype)))
                 for n, x in kv.new_state().items()}
        row = jax.jit(lambda c, w: kv.view(c, w, MAX_LEN))(state, kv.at(1))
        assert set(row) == set(kv.kinds) - {"sparse_counts"}
        for name, leaf in row.items():
            np.testing.assert_array_equal(np.asarray(leaf)[0], np.asarray(state[name])[1])
        changed = {n: x + 1 for n, x in row.items()}
        after = jax.jit(kv.put_piece)(state, changed, kv.at(1))
        for name, kind in kv.kinds.items():
            got, was = np.asarray(after[name]), np.asarray(state[name])
            if kind == "counter":
                np.testing.assert_array_equal(got, was)
                continue
            np.testing.assert_array_equal(got[[0, 2, 3]], was[[0, 2, 3]])
            np.testing.assert_array_equal(got[1], was[1] + 1)

    def test_only_a_layout_with_states_tells_the_family_more(self, kv, model):
        """What the chunk, admit and piece programs pass beyond cache and
        offset: real lengths and live rows where a state would keep what a
        padded tail or an idle slot adds; nothing elsewhere, so the other
        layouts' programs are the ones their compile caches hold."""
        kv, _ = kv
        offsets, steps = jnp.asarray([7, 0, 32, 5]), jnp.asarray([3, 9, 0, 1])
        np.testing.assert_array_equal(np.asarray(kv.step_kwargs(offsets, steps)["live"]),
                                      [True, False, False, True])
        assert int(kv.block_kwargs(last_idx=jnp.int32(11))["valid_len"]) == 12
        assert kv.block_kwargs(valid_len=None) == {"valid_len": None}
        for layout in LAYOUTS:
            other = build(model, layout)
            assert other.step_kwargs(offsets, steps) == {} == other.block_kwargs(last_idx=3)

    def test_counters_ride_home_as_a_row_each(self, kv):
        kv, _ = kv
        state = dict(kv.new_state(), sparse_counts=jnp.asarray([24, 100, 1, 2, 1], jnp.int32))
        block = jnp.zeros((SLOTS, 5), jnp.int32)
        out = np.asarray(kv.ride(state, block))
        assert out.shape == (SLOTS + 5, 5)
        kv._last.clear()
        kv.landed(out)
        sparse = kv.stats["sparse"]
        assert [sparse[k] for k in ("positions_read", "positions_cached", "steps_sparse",
                                    "steps_all", "steps_kernel")] == [24, 100, 1, 2, 1]


class TestTwoStateLeavesALayer:
    """``LayerKindKV`` over a family whose state-space layers keep TWO leaves
    a row with no position axis (nemotron_h: the recurrence's float32 state and
    the convolution's tail), whose one attention layer keeps keys and values,
    and whose expert layers cache nothing."""

    @pytest.fixture(scope="class")
    def kv(self):
        from modelx_tpu.models import nemotron_h

        cfg = nemotron_h.NemotronHConfig.tiny(vocab_size=64)  # M E M * E
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["nemotron_h"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        return kv_layout.build(server, fwd, init_cache, {}, max_slots=SLOTS, max_len=MAX_LEN,
                               chunk_size=4, page_size=0, max_live_tokens=0,
                               paged_attention="gather", prefill_chunk=16)

    def test_both_state_leaves_are_counted_and_the_expert_layers_have_none(self, kv):
        state = kv.new_state()
        assert kv.kinds == {"s0": "state", "t0": "state", "s2": "state", "t2": "state",
                            "k3": "full", "v3": "full", "moe_counts": "counter",
                            "ssm_counts": "counter"}
        assert state["s0"].shape == (SLOTS, 8, 4, 8) and state["s0"].dtype == jnp.float32
        assert state["t2"].shape == (SLOTS, 3, 64) and state["k3"].shape == (SLOTS, MAX_LEN, 16)
        stats = kv.stats["kv"]
        assert stats["bytes_state"] == 2 * SLOTS * (8 * 4 * 8 + 3 * 64) * 4  # states AND tails
        assert stats["bytes_full"] == 2 * SLOTS * MAX_LEN * 16 * 4 and stats["bytes_window"] == 0
        assert kv.has_state and kv.counter_rows == 4 + 3
        assert kv.stats["ssm"] == {"layers": 2, "heads": 8, "head_dim": 4, "state_size": 8,
                                   "groups": 2, "conv_kernel": 4}
        assert kv.stats["moe"]["latent_size"] == 16

    def test_an_admissions_scratch_lands_state_and_tail_whole(self, kv):
        small = scratch(kv, 5, 32)
        state = jax.jit(kv.put)(kv.new_state(), small, kv.at(2))
        for name, kind in kv.kinds.items():
            if kind == "counter":
                continue
            got, want = np.asarray(state[name]), np.asarray(small[name])[0]
            assert not got[[0, 1, 3]].any()
            if kind == "state":
                np.testing.assert_array_equal(got[2], want)
            else:
                np.testing.assert_array_equal(got[2, :32], want)

    def test_a_piece_is_handed_state_and_tail_and_gives_them_back(self, kv):
        rng = np.random.RandomState(3)
        state = {n: (x if kv.kinds[n] == "counter" else
                     jnp.asarray(rng.standard_normal(x.shape).astype(x.dtype)))
                 for n, x in kv.new_state().items()}
        row = jax.jit(lambda c, w: kv.view(c, w, 32))(state, kv.at(1))
        assert set(row) == set(kv.kinds) - {"moe_counts", "ssm_counts"}
        assert row["s0"].shape == (1, 8, 4, 8) and row["t0"].shape == (1, 3, 64)
        assert row["k3"].shape == (1, 32, 16)
        after = jax.jit(kv.put_piece)(state, {n: x + 1 for n, x in row.items()}, kv.at(1))
        for name in ("s0", "t0", "s2", "t2"):
            got, was = np.asarray(after[name]), np.asarray(state[name])
            np.testing.assert_array_equal(got[[0, 2, 3]], was[[0, 2, 3]])
            np.testing.assert_array_equal(got[1], was[1] + 1)

    def test_both_counter_leaves_ride_home_a_row_an_entry(self, kv):
        state = dict(kv.new_state(), moe_counts=jnp.asarray([66, 16, 7, 8], jnp.int32),
                     ssm_counts=jnp.asarray([3, 4, 90], jnp.int32))
        out = np.asarray(kv.ride(state, jnp.zeros((SLOTS, 5), jnp.int32)))
        assert out.shape == (SLOTS + 7, 5)
        kv._last.clear()
        kv.landed(out)
        assert [kv.stats["ssm"][k] for k in ("steps_live", "steps_all", "positions_live")] == [3, 4, 90]
        assert [kv.stats["moe"][k] for k in ("assignments", "assignments_held", "experts_hit",
                                             "experts_read")] == [66, 16, 7, 8]

    @pytest.mark.parametrize("asked,message", [
        ({"page_size": 16}, "--kv-page-size"), ({"prefix_cache": 4}, "--prefix-cache"),
        ({"speculative_k": 2}, "--speculative-k")])
    def test_what_a_state_cannot_carry_is_refused_and_chunked_prefill_is_not(self, asked, message):
        base = dict(page_size=0, prefix_cache=None, prefill_chunk=16, speculative_k=0)
        kinds = ("state", "full", "counter")
        with pytest.raises(kv_layout.Refused, match=message):
            kv_layout.LayerKindKV.refuse("nemotron_h", kinds, **dict(base, **asked))
        kv_layout.LayerKindKV.refuse("nemotron_h", kinds, **base)


class TestLatentLeaves:
    """``LayerKindKV`` over a family whose layers cache one compressed line a
    position (deepseek_v2): laid and addressed as a full leaf — it carries
    ``--prefill-chunk`` — under a kind of its own, so that what no test holds
    over it is refused by name."""

    @pytest.fixture(scope="class")
    def kv(self):
        from modelx_tpu.models import deepseek_v2

        cfg = deepseek_v2.DeepseekV2Config.tiny(vocab_size=64)
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["deepseek_v2"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        return kv_layout.build(server, fwd, init_cache, {}, max_slots=SLOTS, max_len=MAX_LEN,
                               chunk_size=4, page_size=0, max_live_tokens=0,
                               paged_attention="gather", prefill_chunk=16), cfg

    def test_a_line_a_position_a_layer_and_its_bytes_under_their_own_name(self, kv):
        kv, cfg = kv
        state = kv.new_state()
        assert kv.kinds == {"c0": "latent", "c1": "latent", "c2": "latent",
                            "moe_counts": "counter", "mla_counts": "counter"}
        assert state["c0"].shape == (SLOTS, MAX_LEN, 128)  # 32 + 8 values in one lane tile
        stats = kv.stats["kv"]
        assert stats["bytes_latent"] == 3 * SLOTS * MAX_LEN * 128 * 4
        assert stats["bytes_full"] == 0 == stats["bytes_window"] and "bytes_state" not in stats
        assert not kv.has_state and kv.ring == MAX_LEN and kv.counter_rows == 8
        assert kv.row_writes == (0, 0)  # the family writes a row's line itself: nothing to count
        assert kv.stats["mla"]["kv_lora_rank"] == 32 and kv.stats["moe"]["groups"] == 4
        assert kv.step_kwargs(jnp.asarray([1]), jnp.asarray([1])) == {} == kv.block_kwargs(last_idx=3)

    def test_a_piece_is_handed_the_slots_lines_and_gives_them_back(self, kv):
        kv, _ = kv
        rng = np.random.RandomState(4)
        state = {n: (x if kv.kinds[n] == "counter" else
                     jnp.asarray(rng.standard_normal(x.shape).astype(x.dtype)))
                 for n, x in kv.new_state().items()}
        row = jax.jit(lambda c, w: kv.view(c, w, MAX_LEN))(state, kv.at(1))
        assert set(row) == {"c0", "c1", "c2"} and row["c0"].shape == (1, MAX_LEN, 128)
        after = jax.jit(kv.put_piece)(state, {n: x + 1 for n, x in row.items()}, kv.at(1))
        for name in row:
            got, was = np.asarray(after[name]), np.asarray(state[name])
            np.testing.assert_array_equal(got[[0, 2, 3]], was[[0, 2, 3]])
            np.testing.assert_array_equal(got[1], was[1] + 1)
        small = scratch(kv, 5, 32)
        landed = jax.jit(kv.put)(kv.new_state(), small, kv.at(2))
        np.testing.assert_array_equal(np.asarray(landed["c1"])[2, :32], np.asarray(small["c1"])[0])
        assert not np.asarray(landed["c1"])[[0, 1, 3]].any()

    def test_both_counter_leaves_ride_home_in_their_order(self, kv):
        kv, _ = kv
        state = dict(kv.new_state(), moe_counts=jnp.asarray([12, 3, 2, 8], jnp.int32),
                     mla_counts=jnp.asarray([64, 40, 6, 6], jnp.int32))
        out = np.asarray(kv.ride(state, jnp.zeros((SLOTS, 5), jnp.int32)))
        assert out.shape == (SLOTS + 8, 5)
        kv._last.clear()
        kv.landed(out)
        assert [kv.stats["moe"][k] for k in ("assignments", "assignments_held", "experts_hit",
                                             "experts_read")] == [12, 3, 2, 8]
        assert [kv.stats["mla"][k] for k in ("positions_read", "positions_cached",
                                             "steps_absorbed", "steps_all")] == [64, 40, 6, 6]

    @pytest.mark.parametrize("asked,what", [
        (dict(page_size=16), "--kv-page-size"), (dict(speculative_k=2), "--speculative-k"),
        (dict(prefix_cache=object()), "--prefix-cache")])
    def test_what_no_test_holds_over_a_latent_line_is_refused_by_name(self, asked, what):
        base = dict(page_size=0, prefix_cache=None, prefill_chunk=16, speculative_k=0)
        with pytest.raises(kv_layout.Refused) as err:
            kv_layout.LayerKindKV.refuse("deepseek_v2", ("latent", "counter"), **dict(base, **asked))
        assert what in str(err.value) and "'latent' leaves" in str(err.value)
        kv_layout.LayerKindKV.refuse("deepseek_v2", ("latent", "counter"), **base)  # the cell's own


class TestLatentAndIndexLeaves:
    """``LayerKindKV`` over deepseek_v2 with an indexer (DeepSeek-V3.2): two
    position-addressed leaves a layer — the latent line ``c<i>`` (kind
    ``"latent"``) and the index key ``i<i>`` (kind ``"index"`` at one row a
    position) — viewed, landed and handed to a piece together; the refusals
    are the latent line's."""

    @pytest.fixture(scope="class")
    def kv(self):
        from modelx_tpu.models import deepseek_v2

        cfg = deepseek_v2.DeepseekV2Config.tiny_v32(vocab_size=64)
        server = types.SimpleNamespace(mesh=make_mesh("dp=1", jax.devices()[:1]),
                                       family=FAMILIES["deepseek_v2"], cfg=cfg)
        fwd, init_cache = server.family.decode_fns(cfg, mesh=server.mesh)
        return kv_layout.build(server, fwd, init_cache, {}, max_slots=SLOTS, max_len=MAX_LEN,
                               chunk_size=4, page_size=0, max_live_tokens=0,
                               paged_attention="gather", prefill_chunk=16), cfg

    def test_two_leaves_a_layer_and_each_kinds_bytes_under_its_own_name(self, kv):
        kv, cfg = kv
        state = kv.new_state()
        assert [kv.kinds[f"c{i}"] for i in range(3)] == ["latent"] * 3
        assert [kv.kinds[f"i{i}"] for i in range(3)] == ["index"] * 3
        assert {n for n, k in kv.kinds.items() if k == "counter"} == {
            "moe_counts", "mla_counts", "dsa_counts"}
        assert state["c0"].shape == (SLOTS, MAX_LEN, 128) and state["i0"].shape == (
            SLOTS, MAX_LEN, cfg.index_dim)
        stats = kv.stats["kv"]
        assert stats["bytes_latent"] == 3 * SLOTS * MAX_LEN * 128 * 4
        assert stats["bytes_index"] == 3 * SLOTS * MAX_LEN * cfg.index_dim * 4
        assert stats["bytes_full"] == 0 == stats["bytes_state"]
        assert not kv.has_state and kv.counter_rows == 13
        assert kv.stats["dsa"] == {"layers": 3, "index_topk": 8, "index_heads": 4, "index_dim": 16}
        assert kv.step_kwargs(jnp.asarray([1]), jnp.asarray([1])) == {} == kv.block_kwargs(last_idx=3)

    def test_a_piece_is_handed_both_leaves_and_gives_both_back(self, kv):
        kv, _ = kv
        rng = np.random.RandomState(4)
        state = {n: (x if kv.kinds[n] == "counter" else
                     jnp.asarray(rng.standard_normal(x.shape).astype(x.dtype)))
                 for n, x in kv.new_state().items()}
        row = jax.jit(lambda c, w: kv.view(c, w, 32))(state, kv.at(1))
        assert set(row) == {f"{k}{i}" for k in "ci" for i in range(3)}
        assert row["c0"].shape == (1, 32, 128) and row["i2"].shape == (1, 32, 16)
        np.testing.assert_array_equal(np.asarray(row["i2"])[0], np.asarray(state["i2"])[1, :32])
        after = jax.jit(kv.put_piece)(state, {n: x + 1 for n, x in row.items()}, kv.at(1))
        for name in row:
            got, was = np.asarray(after[name]), np.asarray(state[name])
            np.testing.assert_array_equal(got[[0, 2, 3]], was[[0, 2, 3]])
            np.testing.assert_array_equal(got[1, :32], was[1, :32] + 1)
            np.testing.assert_array_equal(got[1, 32:], was[1, 32:])

    def test_the_three_counter_leaves_ride_home_in_their_order(self, kv):
        kv, _ = kv
        state = dict(kv.new_state(), moe_counts=jnp.asarray([12, 3, 2, 8], jnp.int32),
                     mla_counts=jnp.asarray([16, 40, 6, 6], jnp.int32),
                     dsa_counts=jnp.asarray([40, 16, 2, 6, 2], jnp.int32))
        out = np.asarray(kv.ride(state, jnp.zeros((SLOTS, 5), jnp.int32)))
        assert out.shape == (SLOTS + 13, 5)
        kv._last.clear()
        kv.landed(out)
        assert [kv.stats["dsa"][k] for k in (
            "positions_scored", "lines_selected", "steps_selecting", "steps_all",
            "steps_kernel")] == [40, 16, 2, 6, 2]
        assert kv.stats["mla"]["positions_read"] == 16 and kv.stats["moe"]["experts_read"] == 8

    @pytest.mark.parametrize("asked,what", [
        (dict(page_size=16), "--kv-page-size"), (dict(speculative_k=2), "--speculative-k"),
        (dict(prefix_cache=object()), "--prefix-cache")])
    def test_the_latent_lines_refusals_hold_with_an_index_beside_them(self, asked, what):
        base = dict(page_size=0, prefix_cache=None, prefill_chunk=2048, speculative_k=0)
        kinds = ("latent", "index", "counter")
        with pytest.raises(kv_layout.Refused) as err:
            kv_layout.LayerKindKV.refuse("deepseek_v2", kinds, **dict(base, **asked))
        assert what in str(err.value) and "'latent' leaves" in str(err.value)
        kv_layout.LayerKindKV.refuse("deepseek_v2", kinds, **base)  # the cell's own
