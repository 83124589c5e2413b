"""repro.state subsystem tests: slot registry, StateTree, bucket-
invariant EF layout, checkpoint portability across pipeline settings,
slot-diff migration, tuner state pricing, and the fused warmup Adam."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import LAYOUTS, TwoStageOptimizer, get_optimizer
from repro.state import (SlotSpec, StateLayout, StateTree,
                         bucket_sizes_for, canonicalize_state,
                         ef_element_map, ef_slot_perm, layout_manifest,
                         slot_length, state_bytes)

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = REPO_SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def topk_adam() -> TwoStageOptimizer:
    """1-bit Adam over a sparse compressor: the one kind of optimizer
    whose hierarchical exchange runs the cross-pod EF loops."""
    return get_optimizer("onebit_adam", compressor="topk",
                         compressor_kwargs={"block_size": 256, "ratio": 8})


class TestSlotRegistry:
    def test_base_family_slots(self):
        opt = TwoStageOptimizer()
        names = [s.name for s in opt.state_slots()]
        assert names == ["m", "v", "worker_err", "server_err", "scale",
                         "count", "v_step"]
        by = {s.name: s for s in opt.state_slots()}
        assert by["worker_err"].ef == "worker"
        assert by["server_err"].bucket_keyed
        assert by["count"].dtype == "int32"
        # the hierarchical schedule of a sparse compressor consumes the
        # cross-pod EF slots: declared there and nowhere else
        hier = {s.name: s for s in topk_adam().state_slots("replicated",
                                                           "hier")}
        assert list(hier)[len(names):] == ["outer_err", "outer_ag_err"]
        assert hier["outer_ag_err"].chunk_of == "total"

    @pytest.mark.parametrize("optimizer,compressor,topology,outer", [
        ("onebit_adam", "onebit", "flat", False),
        ("onebit_adam", "onebit", "hier", False),
        ("onebit_adam", "topk", "flat", False),
        ("onebit_adam", "topk", "hier", True),
        ("zerone_adam", "onebit", "hier", False),
        ("onebit_lamb", "identity", "hier", False)])
    def test_outer_slots_only_where_consumed(self, optimizer, compressor,
                                             topology, outer):
        """A state holds the cross-pod EF slots only where its schedule
        consumes them (``plan.schedules.needs_outer_ef`` on ``hier``);
        elsewhere they would be full-length zeros the step never reads."""
        from repro.plan import needs_outer_ef
        opt = get_optimizer(optimizer, compressor=compressor,
                            compressor_kwargs={"block_size": 256})
        both = {"outer_err", "outer_ag_err"}
        for layout in LAYOUTS:
            names = {s.name for s in opt.state_slots(layout, topology)}
            assert names & both == (both if outer else set())
        assert outer == (topology == "hier"
                         and needs_outer_ef(opt.compressor))
        st = opt.init_state(4096, 8, n_inner=4 if topology == "hier"
                            else None)
        assert ("outer_err" in st and "outer_ag_err" in st) is outer

    def test_zero1_layout_swaps_v_for_shards(self):
        by = {s.name: s for s in TwoStageOptimizer().state_slots("zero1")}
        assert "v" not in by
        assert by["v_shard"].replication == "dp_sharded"
        assert by["v_shard"].chunk_of == "dp"
        assert by["master_shard"].extent == "per_chunk"
        # EF slots identical across layouts: error state is per-worker
        assert by["worker_err"].replication == "per_dp_rank"

    def test_local_layout_per_rank_adaptive_state(self):
        by = {s.name: s for s in TwoStageOptimizer().state_slots("local")}
        for n in ("m", "v", "scale"):
            assert by[n].replication == "per_dp_rank", n

    def test_slot_lengths_by_extent(self):
        ctx = StateLayout(d=1024, n_dp=8, n_srv=4, n_outer=2,
                          n_segments=5)
        assert slot_length(SlotSpec("a", "per_param"), ctx) == 1024
        assert slot_length(SlotSpec("b", "per_chunk", chunk_of="dp"),
                           ctx) == 128
        assert slot_length(SlotSpec("c", "per_chunk", chunk_of="server"),
                           ctx) == 256
        assert slot_length(SlotSpec("d", "per_chunk", chunk_of="total"),
                           ctx) == 128
        assert slot_length(SlotSpec("e", "per_segment"), ctx) == 5
        assert slot_length(SlotSpec("f", "scalar", dtype="int32"),
                           ctx) is None

    def test_state_bytes_zero1_smaller_per_rank(self):
        opt = TwoStageOptimizer()
        ctx = StateLayout(d=1 << 20, n_dp=16, n_srv=16)
        rep = state_bytes(opt.state_slots("replicated"), ctx)
        z1 = state_bytes(opt.state_slots("zero1"), ctx)
        # replicated: m+v+worker = 3d full; zero1: m+worker full, v+master
        # as d/16 shards
        assert z1 < rep
        assert rep - z1 == pytest.approx(4 * (1 << 20) * (1 - 2 / 16),
                                         rel=0.01)

    def test_invalid_specs_rejected(self):
        with pytest.raises(AssertionError):
            SlotSpec("x", "scalar", "per_dp_rank")
        with pytest.raises(AssertionError):
            SlotSpec("x", "per_param", bucket_keyed=True)
        with pytest.raises(AssertionError):
            SlotSpec("x", extent="per_widget")


class TestStateTree:
    def test_attr_access_replace_and_immutability(self):
        st = StateTree(m=jnp.zeros(4), count=jnp.int32(0))
        assert st.m.shape == (4,)
        st2 = st._replace(count=jnp.int32(3))
        assert int(st2.count) == 3 and int(st.count) == 0
        with pytest.raises(AssertionError):
            st._replace(nope=1)
        with pytest.raises(AttributeError):
            st.m = jnp.ones(4)
        with pytest.raises(AttributeError):
            st.missing

    def test_pytree_roundtrip_preserves_type_and_order(self):
        st = StateTree(b=jnp.zeros(2), a=jnp.ones(3))
        leaves, treedef = jax.tree.flatten(st)
        back = jax.tree.unflatten(treedef, leaves)
        assert isinstance(back, StateTree)
        assert list(back) == ["b", "a"]          # insertion order kept
        mapped = jax.tree.map(lambda x: x * 2, st)
        assert isinstance(mapped, StateTree)
        np.testing.assert_array_equal(np.asarray(mapped.a),
                                      2 * np.ones(3))

    def test_checkpoint_keys_match_namedtuple_era(self):
        """StateTree key paths flatten as GetAttrKey, so the npz leaf
        keys are identical to what the old NamedTuple containers
        produced — old checkpoints need no key translation."""
        class Old(NamedTuple):
            m: object
            v: object

        from repro.checkpoint.io import _flatten_with_paths
        old_keys, _ = _flatten_with_paths((Old(m=jnp.zeros(2),
                                               v=jnp.zeros(2)),))
        new_keys, _ = _flatten_with_paths((StateTree(m=jnp.zeros(2),
                                                     v=jnp.zeros(2)),))
        assert sorted(old_keys) == sorted(new_keys)


class TestElementMap:
    def test_tiny_hand_example(self):
        # d=8, two buckets (4,4), n_srv=2: rank r serves, per bucket,
        # its contiguous half of the bucket
        m = ef_element_map(8, (4, 4), n_srv=2)
        np.testing.assert_array_equal(m[0, 0], [0, 1, 4, 5])
        np.testing.assert_array_equal(m[0, 1], [2, 3, 6, 7])
        # serial keying: one contiguous chunk per rank
        s = ef_element_map(8, (8,), n_srv=2)
        np.testing.assert_array_equal(s[0, 0], [0, 1, 2, 3])

    def test_map_is_permutation_and_subchunked(self):
        sizes = (1024, 1536, 1536)   # uneven
        m = ef_element_map(4096, sizes, n_srv=4, n_sub=2)
        assert m.shape == (2, 4, 512)
        assert sorted(m.reshape(-1).tolist()) == list(range(4096))

    def test_perm_roundtrip_identity(self):
        d, sizes = 4096, (1024, 3072)
        fwd = ef_slot_perm(d, sizes, n_srv=4)
        back = ef_slot_perm(d, (d,), n_srv=4, canonical_sizes=sizes)
        x = np.random.default_rng(0).normal(size=d).astype(np.float32)
        np.testing.assert_array_equal(x[fwd][back], x)

    def test_canonicalize_moves_values_to_serial_owner(self):
        """Write each buffer position's GLOBAL ELEMENT INDEX into the
        run layout; canonicalisation must land element e at the serial
        position of e's serial owner."""
        d, n_srv, nb = 2048, 4, 3
        block, n_dp = 64, 4
        sizes = bucket_sizes_for(d, n_dp, block, nb)
        slots = (SlotSpec("server_err", "per_chunk",
                          replication="per_dp_rank", chunk_of="server",
                          ef="server", bucket_keyed=True),)
        ctx = StateLayout(d=d, n_dp=n_dp, n_srv=n_srv, dp_sizes=(4,),
                          tp=1)
        run_map = ef_element_map(d, sizes, n_srv)[0]     # (4, 512)
        state = StateTree(server_err=run_map.astype(np.float32)
                          .reshape(4, 1, 512))
        canon = canonicalize_state(state, slots, ctx, n_buckets=nb,
                                   block=block)
        want = ef_element_map(d, (d,), n_srv)[0].astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(canon.server_err).reshape(4, 512), want)
        # and back
        back = canonicalize_state(canon, slots, ctx, n_buckets=nb,
                                  block=block, to_canonical=False)
        np.testing.assert_array_equal(np.asarray(back.server_err),
                                      np.asarray(state.server_err))

    def test_serial_is_canonical_noop(self):
        slots = TwoStageOptimizer().state_slots()
        ctx = StateLayout(d=2048, n_dp=4, n_srv=4, dp_sizes=(4,), tp=1)
        from repro.state import init_global_state
        st = init_global_state(slots, ctx)
        out = canonicalize_state(st, slots, ctx, n_buckets=1, block=64)
        assert out is st


class TestGlobalMaterialisation:
    def _mesh(self, shape, axes):
        from repro.launch.mesh import make_mesh
        return make_mesh(shape, axes)

    def test_shapes_and_specs_match_hand_table(self):
        """The derived global shapes/PartitionSpecs must equal the old
        hand-written layout table for every (layout x topology) — here
        on a synthetic 2-pod x 2-dp hier context (no devices needed)."""
        from jax.sharding import PartitionSpec as P
        from repro.state import (global_shapes, init_global_state,
                                 state_specs)
        opt = TwoStageOptimizer()
        d, S = 8192, 7
        ctx = StateLayout(d=d, n_dp=4, n_srv=2, n_outer=2, n_segments=S,
                          dp_sizes=(2, 2), tp=1)
        st = init_global_state(opt.state_slots("replicated"), ctx)
        assert st.m.shape == (1, d)
        assert st.v.shape == (1, d)
        assert st.worker_err.shape == (2, 2, 1, d)
        assert st.server_err.shape == (2, 2, 1, d // 2)   # inner size 2
        outer = init_global_state(
            topk_adam().state_slots("replicated", "hier"), ctx)
        assert outer.outer_err.shape == (2, 2, 1, d // 2)
        assert outer.outer_ag_err.shape == (2, 2, 1, d // 4)
        assert st.scale.shape == (1, S)
        assert st.count.shape == () and st.count.dtype == jnp.int32
        sp = state_specs(opt.state_slots("replicated"), ("pod", "data"))
        assert sp.m == P("model", None)
        assert sp.worker_err == P("pod", "data", "model", None)
        assert sp.count == P()
        z = init_global_state(opt.state_slots("zero1"), ctx)
        assert z.v_shard.shape == (2, 2, 1, d // 4)       # FULL dp shard
        assert z.master_shard.shape == (2, 2, 1, d // 4)
        assert z.m.shape == (1, d)
        loc = init_global_state(opt.state_slots("local"), ctx)
        assert loc.m.shape == (2, 2, 1, d)
        assert loc.scale.shape == (2, 2, 1, S)
        # shape table via the real mesh-derived path (1x1 mesh)
        from repro.configs import get_config
        from repro.train.step import init_train_state, train_state_specs
        cfg = get_config("internlm2-1.8b").reduced()
        mesh = self._mesh((1, 1), ("data", "model"))
        st1 = init_train_state(cfg, mesh, block=512)
        assert st1.worker_err.shape[0] == 1       # (dp=1, tp=1, d)
        sp1 = train_state_specs(mesh)
        assert sp1.server_err == P("data", "model", None)

    def test_abstract_matches_concrete(self):
        from repro.configs import get_config
        from repro.train.step import init_train_state
        cfg = get_config("internlm2-1.8b").reduced()
        mesh = self._mesh((1, 1), ("data", "model"))
        for layout in LAYOUTS:
            a = init_train_state(cfg, mesh, block=512, abstract=True,
                                 layout=layout)
            c = init_train_state(cfg, mesh, block=512, layout=layout)
            for k in a:
                assert a[k].shape == c[k].shape, (layout, k)
                assert a[k].dtype == c[k].dtype, (layout, k)


class TestCheckpointMigration:
    def test_pre_plan_ir_namedtuple_checkpoint_loads(self):
        """Regression (satellite): a pre-PR2-era checkpoint — NamedTuple
        state container, no outer EF slots — must load into the
        registry-built template of a run that declares them (hier over
        a sparse compressor) with the missing slots named from the slot
        diff and zero-initialised."""
        class PrePlanIRState(NamedTuple):   # the PR-1-era container
            m: object
            v: object
            worker_err: object
            server_err: object
            scale: object
            count: object
            v_step: object

        from repro.checkpoint.io import save_pytree
        from repro.state import load_train_state
        d, n = 1024, 4
        opt = TwoStageOptimizer()
        rng = np.random.default_rng(0)
        old = PrePlanIRState(
            m=rng.normal(size=(1, d)).astype(np.float32),
            v=np.abs(rng.normal(size=(1, d))).astype(np.float32),
            worker_err=rng.normal(size=(n, 1, d)).astype(np.float32),
            server_err=rng.normal(size=(n, 1, d // n)).astype(np.float32),
            scale=np.zeros((1, 3), np.float32),
            count=np.int32(7), v_step=np.int32(0))
        params = {"w": rng.normal(size=(4,)).astype(np.float32)}
        ctx = StateLayout(d=d, n_dp=n, n_srv=n, n_segments=3,
                          dp_sizes=(n,), tp=1)
        slots = topk_adam().state_slots("replicated", "hier")
        from repro.state import init_global_state
        template = init_global_state(slots, ctx)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "old.npz")
            save_pytree(path, (params, old), step=7)
            with pytest.warns(UserWarning, match="outer_ag_err"):
                (p2, st), step = load_train_state(
                    path, params, template, slots=slots, ctx=ctx,
                    n_buckets=1, block=256)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(st.m), old.m)
        np.testing.assert_array_equal(np.asarray(st.server_err),
                                      old.server_err)
        np.testing.assert_array_equal(np.asarray(st.outer_err),
                                      np.zeros((n, 1, d // n)))
        np.testing.assert_array_equal(np.asarray(st.outer_ag_err),
                                      np.zeros((n, 1, d // n)))

    @staticmethod
    def _outer_ckpt(tmp, save_slots, load_slots, **fill):
        """Saves a zero state of ``save_slots`` with ``fill`` written
        over some slots, and loads it into a zero state of
        ``load_slots``; returns (saved, loaded)."""
        from repro.state import (init_global_state, load_train_state,
                                 save_train_state)
        ctx = StateLayout(d=2048, n_dp=4, n_srv=4, dp_sizes=(4,), tp=1)
        st = init_global_state(save_slots, ctx)
        st = st._replace(**{k: jnp.full(st[k].shape, v)
                            for k, v in fill.items()})
        path = os.path.join(tmp, "ck.npz")
        save_train_state(path, {"w": np.zeros(2)}, st, 9, slots=save_slots,
                         ctx=ctx, n_buckets=1, block=64)
        (_, got), step = load_train_state(
            path, {"w": np.zeros(2)}, init_global_state(load_slots, ctx),
            slots=load_slots, ctx=ctx, n_buckets=1, block=64)
        assert step == 9
        assert list(got) == [s.name for s in load_slots]
        return st, got

    def test_outer_slots_dropped_and_made(self):
        """A checkpoint that holds zero cross-pod EF slots (every state
        declared them before they became topology-dependent) loads,
        without a word, into a flat 1-bit run's state, which lacks them;
        the reverse load makes them, as zeros, and names them."""
        import warnings
        flat = TwoStageOptimizer().state_slots()
        hier = topk_adam().state_slots("replicated", "hier")
        with tempfile.TemporaryDirectory() as tmp:
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                st, got = self._outer_ckpt(tmp, hier, flat, m=0.5,
                                           server_err=0.25)
            np.testing.assert_array_equal(got.m, np.asarray(st.m))
            np.testing.assert_array_equal(got.server_err,
                                          np.asarray(st.server_err))
            with pytest.warns(UserWarning, match="outer_ag_err"):
                _, got = self._outer_ckpt(tmp, flat, hier, worker_err=1.0)
        np.testing.assert_array_equal(got.worker_err, 1.0)
        np.testing.assert_array_equal(got.outer_err, 0.0)
        np.testing.assert_array_equal(got.outer_ag_err, 0.0)

    def test_nonzero_outer_slots_dropped_with_a_warning(self):
        flat = TwoStageOptimizer().state_slots()
        hier = topk_adam().state_slots("replicated", "hier")
        with tempfile.TemporaryDirectory() as tmp:
            with pytest.warns(UserWarning, match=r"\['outer_err'\]"):
                self._outer_ckpt(tmp, hier, flat, outer_err=0.5)

    def test_save_canonical_load_rebuckets(self):
        """save under 4 buckets -> the archive holds the canonical
        (serial) keying; loading under 3 buckets scatters into the new
        partition — per-element content preserved end to end."""
        from repro.state import load_train_state, save_train_state
        from repro.checkpoint.io import load_meta
        d, n, block = 4096, 4, 64
        opt = TwoStageOptimizer()
        slots = opt.state_slots()
        ctx = StateLayout(d=d, n_dp=n, n_srv=n, dp_sizes=(n,), tp=1)
        from repro.state import init_global_state
        st = init_global_state(slots, ctx)
        sizes4 = bucket_sizes_for(d, n, block, 4)
        run4 = ef_element_map(d, sizes4, n)[0].astype(np.float32)
        st = st._replace(server_err=jnp.asarray(
            run4.reshape(n, 1, d // n)))
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "ck.npz")
            save_train_state(path, {"w": np.zeros(2)}, st, 5,
                             slots=slots, ctx=ctx, n_buckets=4,
                             block=block)
            meta = load_meta(path)
            assert meta["ef_layout"] == "canonical"
            with np.load(path) as data:
                canon = data["1|.server_err"].reshape(n, d // n)
            want = ef_element_map(d, (d,), n)[0].astype(np.float32)
            np.testing.assert_array_equal(canon, want)
            (_, st3), step = load_train_state(
                path, {"w": np.zeros(2)}, init_global_state(slots, ctx),
                slots=slots, ctx=ctx, n_buckets=3, block=block)
        sizes3 = bucket_sizes_for(d, n, block, 3)
        run3 = ef_element_map(d, sizes3, n)[0].astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(st3.server_err).reshape(n, d // n), run3)
        assert step == 5

    def test_bucket_major_era_checkpoint_lifts_to_canonical(self):
        """A checkpoint saved by the bucket-major era (meta n_buckets=k,
        no canonical flag) is canonicalised from k on load."""
        from repro.checkpoint.io import save_pytree
        from repro.state import init_global_state, load_train_state
        d, n, block = 4096, 4, 64
        slots = TwoStageOptimizer().state_slots()
        ctx = StateLayout(d=d, n_dp=n, n_srv=n, dp_sizes=(n,), tp=1)
        st = init_global_state(slots, ctx)
        sizes2 = bucket_sizes_for(d, n, block, 2)
        run2 = ef_element_map(d, sizes2, n)[0].astype(np.float32)
        st = st._replace(server_err=jnp.asarray(
            run2.reshape(n, 1, d // n)))
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "old.npz")
            # old-era save: raw bucket-major arrays + n_buckets meta
            save_pytree(path, ({"w": np.zeros(2)}, st), 3,
                        meta={"n_buckets": 2})
            (_, st1), _ = load_train_state(
                path, {"w": np.zeros(2)}, init_global_state(slots, ctx),
                slots=slots, ctx=ctx, n_buckets=1, block=block)
        want = ef_element_map(d, (d,), n)[0].astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(st1.server_err).reshape(n, d // n), want)


class TestLayoutManifest:
    def test_manifest_deterministic_and_complete(self):
        opt = topk_adam()
        ctx = StateLayout(d=1 << 16, n_dp=8, n_srv=4, n_outer=2,
                          n_segments=4, dp_sizes=(2, 4), tp=1)
        m1 = layout_manifest(opt.state_slots("zero1", "hier"), ctx,
                             block=1024)
        m2 = layout_manifest(opt.state_slots("zero1", "hier"), ctx,
                             block=1024)
        assert json.dumps(m1, sort_keys=True) == json.dumps(m2,
                                                            sort_keys=True)
        names = [row["name"] for row in m1["slots"]]
        assert "master_shard" in names and "outer_ag_err" in names
        assert m1["state_bytes_per_rank"] > 0
        assert set(m1["bucketed_layouts"]) == {"1", "2", "4"}

    def test_benchmark_script_runs(self):
        from benchmarks.state_manifest import build_manifest
        man = build_manifest(d=1 << 16, n_inner=2, n_outer=2, block=1024)
        assert set(man["grid"]) == {f"{l}/{t}" for l in LAYOUTS
                                    for t in ("flat", "hier")}


class TestTunerStatePricing:
    def test_candidate_carries_slot_registry_bytes(self):
        from repro.plan import get_cluster
        from repro.plan.tune import autotune, layout_state_bytes
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        d = 1 << 20
        rep = layout_state_bytes(spec, d, "flat", "replicated")
        z1 = layout_state_bytes(spec, d, "flat", "zero1")
        assert z1 < rep
        res = autotune(spec, d, compressors=["onebit"],
                       block_sizes=[4096], layouts=["replicated"])
        assert res.best.state_bytes_per_rank == \
            layout_state_bytes(spec, res.best.d_padded,
                               res.best.topology, "replicated")

    def test_state_budget_forces_zero1(self):
        """With both layouts enumerated, replicated wins the tie-break
        until the per-rank state budget excludes it — then the tuner
        shards (the decision the slot extents price)."""
        from repro.plan import get_cluster
        from repro.plan.tune import autotune, layout_state_bytes
        spec = get_cluster("ethernet-10g", n_inner=4, n_outer=2)
        d = 1 << 20
        free = autotune(spec, d, compressors=["onebit"],
                        block_sizes=[4096],
                        layouts=["replicated", "zero1"])
        assert free.best.layout == "replicated"
        budget = layout_state_bytes(spec, free.best.d_padded, "flat",
                                    "replicated") - 1
        tight = autotune(spec, d, compressors=["onebit"],
                         block_sizes=[4096],
                         layouts=["replicated", "zero1"],
                         max_state_bytes_per_rank=budget)
        assert tight.best.layout == "zero1"
        whys = {c.why for c in tight.table
                if not c.valid and c.layout == "replicated"}
        assert "over state-memory budget" in whys


class TestFusedWarmupAdam:
    """Satellite: kernels/fused_adam wired into the warmup stage behind
    ``use_kernel`` — bitwise the jnp chain, and the ``adam_update_cost``
    pricing the kernel was carrying is exercised by a real routing."""

    def test_warmup_matches_jnp_to_the_ulp(self):
        """Same math, same order of operations — pinned at the SAME
        tolerance tests/test_kernels.py pins kernel-vs-ref parity at
        (interpret-mode Pallas and the XLA jnp chain contract FMAs
        differently at the ULP level; observed max ~2.4e-7 abs)."""
        d = 3 * 8192 + 512   # forces the kernel's tile padding path
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        for wd in (0.0, 0.01):
            o_j = get_optimizer("onebit_adam", weight_decay=wd)
            o_k = get_optimizer("onebit_adam", weight_decay=wd,
                                use_kernel=True)
            assert o_k._fused_warmup_ok and not o_j._fused_warmup_ok
            st = o_j.init_state(d, 1)
            st = st._replace(m=jnp.asarray(
                rng.normal(size=(d,)).astype(np.float32)) * 0.1,
                v=jnp.abs(jnp.asarray(
                    rng.normal(size=(d,)).astype(np.float32))) + 0.01)
            xj, sj, mj = o_j.warmup_update(g, st, x, jnp.float32(1e-3))
            xk, sk, mk = o_k.warmup_update(g, st, x, jnp.float32(1e-3))
            for a, b in ((xj, xk), (sj.m, sk.m), (sj.v, sk.v)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=5e-7)
            assert int(sk.count) == 1
            # the stats contract is unchanged by the routing
            assert set(mj) == set(mk)

    def test_fused_gate_respects_hooks(self):
        # bias correction and LAMB's direction hook disable the fusion
        assert not get_optimizer("onebit_adam", use_kernel=True,
                                 bias_correction=True)._fused_warmup_ok
        assert not get_optimizer("onebit_lamb",
                                 use_kernel=True)._fused_warmup_ok
        assert get_optimizer("zerone_adam",
                             use_kernel=True)._fused_warmup_ok

    def test_with_kernels_toggles_optimizer_flag(self):
        opt = get_optimizer("onebit_adam")
        on = opt.with_kernels(True)
        assert on.use_kernel and on.compressor.use_kernel
        off = on.with_kernels(False)
        assert not off.use_kernel and not off.compressor.use_kernel

    def test_step_config_routes_kernel_to_warmup(self):
        from repro.train.step import TrainStepConfig
        tsc = TrainStepConfig(use_kernel="on")
        opt = tsc.build_optimizer()
        assert opt.use_kernel and opt._fused_warmup_ok

    def test_adam_update_cost_pricing_exercised(self):
        """The priced fused-vs-unfused decision matches the routing:
        fused is cheaper on every preset (memory-bound elementwise)."""
        from repro.perf import adam_update_cost, get_device
        d = 1 << 22
        for dev in ("tpu-v5e", "cpu-host"):
            spec = get_device(dev)
            assert adam_update_cost(d, fused=True).time(spec) < \
                adam_update_cost(d, fused=False).time(spec)

    def test_kernel_sweep_fits_peak_flops(self):
        """Satellite: the compute-bound matmul op makes peak_flops a
        fitted quantity — synthetic samples from a known roofline are
        recovered by the 3-term least squares."""
        from benchmarks.kernel_sweep import fit_device
        truth = {"kernel_overhead": 3e-6, "hbm_bw": 5e11,
                 "peak_flops": 2e14}
        samples = []
        for k, hb, fl in ((1, 1e6, 0.0), (1, 64e6, 0.0), (6, 1e6, 0.0),
                          (6, 64e6, 0.0), (1, 12e6, 2e12),
                          (1, 12e6, 16e12)):
            samples.append({"op": "synth", "d": 0, "kernels": k,
                            "hbm_bytes": hb, "flops": fl,
                            "seconds": k * truth["kernel_overhead"]
                            + hb / truth["hbm_bw"]
                            + fl / truth["peak_flops"]})
        fit = fit_device(samples)
        assert fit["kernel_overhead"] == pytest.approx(3e-6, rel=1e-5)
        assert fit["hbm_bw"] == pytest.approx(5e11, rel=1e-5)
        assert fit["peak_flops"] == pytest.approx(2e14, rel=1e-5)
        assert fit["clamped"] == []


class TestDistributedStateInvariance:
    """Multi-device pins of the bucket-invariant layout (subprocess with
    forced host devices, like tests/test_distributed.py)."""

    def test_hier_topk_canonical_ef_equal_across_bucket_counts(self):
        """≥3 chained hier+topk exchanges, serial vs UNEVEN buckets:
        outputs bitwise AND every chunk EF slot per-element equal once
        both runs are mapped to the canonical keying — the invariant
        the checkpoint portability rides on."""
        out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.comm import compressed_allreduce_hierarchical
        from repro.launch.mesh import make_mesh
        from repro.optim import get_compressor
        from repro.state import bucket_sizes_for, ef_slot_perm

        mesh = make_mesh((2, 4), ("pod", "data"))
        block, NB = 128, 3
        n_in, n_out = 4, 2
        d = 5 * 8 * block       # 5 units -> 3 UNEVEN buckets (1,2,2)
        comp = get_compressor("topk", block_size=block, ratio=4)
        rng = np.random.default_rng(5)
        xs = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))

        def run(nb):
            errs = {"worker": jnp.zeros((2, 4, d)),
                    "server": jnp.zeros((2, 4, d // n_in)),
                    "outer": jnp.zeros((2, 4, d // n_in)),
                    "outer_ag": jnp.zeros((2, 4, d // (n_in * n_out)))}

            def body(x, we, se, oe, oae):
                o, e = compressed_allreduce_hierarchical(
                    x[0, 0], {"worker": we[0, 0], "server": se[0, 0],
                              "outer": oe[0, 0], "outer_ag": oae[0, 0]},
                    inner_axes=("data",), outer_axes=("pod",),
                    cfg=comp, n_buckets=nb)
                l = lambda a: a[None, None]
                return (l(o), l(e["worker"]), l(e["server"]),
                        l(e["outer"]), l(e["outer_ag"]))

            specs = (P("pod", "data", None),) * 5
            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=specs,
                                      out_specs=specs, check_vma=False))
            outs, x = [], xs
            for t in range(3):
                o, *e = f(x, errs["worker"], errs["server"],
                          errs["outer"], errs["outer_ag"])
                errs = dict(zip(["worker", "server", "outer",
                                 "outer_ag"], e))
                outs.append(np.asarray(o))
                x = 0.9 * x + 0.1 * xs
            return outs, errs

        o1, e1 = run(1)
        o2, e2 = run(NB)
        for t, (a, b) in enumerate(zip(o1, o2)):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(e1["worker"]),
                                      np.asarray(e2["worker"]))
        print("OK outputs bitwise over 3 exchanges")

        sizes = bucket_sizes_for(d, 8, block, NB)
        assert len(set(sizes)) > 1, sizes     # really uneven

        def canon(errs, nb):
            out = {}
            s = bucket_sizes_for(d, 8, block, nb)
            perm_srv = ef_slot_perm(d, s, n_in)
            for name in ("server", "outer"):      # per pod slice
                a = np.asarray(errs[name])
                out[name] = np.stack([a[p].reshape(-1)[perm_srv]
                                      for p in range(2)])
            perm_ag = ef_slot_perm(d, s, n_in, n_out)
            out["outer_ag"] = np.asarray(
                errs["outer_ag"]).reshape(-1)[perm_ag]
            return out

        c1, c2 = canon(e1, 1), canon(e2, NB)
        for name in ("server", "outer", "outer_ag"):
            np.testing.assert_array_equal(c1[name], c2[name])
        # the run layouts genuinely differed where content exists
        # (hier+topk's server slot stays zero: the inner gather
        # re-compresses an already-sparsified chunk losslessly)
        for name in ("outer", "outer_ag"):
            assert np.count_nonzero(np.asarray(e1[name])) > 0, name
            assert not np.array_equal(np.asarray(e1[name]),
                                      np.asarray(e2[name])), name
        print("OK canonical EF equal, run layouts differ")
        """)
        assert out.count("OK") == 2

    def test_launch_checkpoint_portable_across_pipeline(self):
        """Satellite: save under --pipeline 4, resume under off / 3 / 4
        — params, momentum, variance and worker EF bitwise identical
        across the resumed runs; the chunk EF slots agree once
        canonicalised."""
        out = run_with_devices("""
        import os, tempfile
        import jax, numpy as np
        from repro.launch.train import run
        from repro.optim import get_optimizer
        from repro.state import StateTree, canonicalize_state
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.train.step import state_layout_ctx

        tmp = tempfile.mkdtemp()
        ck = os.path.join(tmp, "ck.npz")
        kw = dict(batch=4, seq=64, mesh_shape=(4, 1), base_lr=2e-3,
                  lr_warmup=2, warmup_steps=2, block_size=512,
                  log_every=100)
        run("internlm2-1.8b-smoke", steps=4, ckpt=ck, pipeline=4, **kw)
        outs = {}
        for pipe in ("off", 3, 4):
            outs[pipe] = run("internlm2-1.8b-smoke", steps=7,
                             resume=ck, pipeline=pipe, **kw)
        ref_p, ref_o, ref_h = outs["off"]
        cfg = get_config("internlm2-1.8b-smoke")
        mesh = make_mesh((4, 1), ("data", "model"))
        ctx = state_layout_ctx(cfg, mesh, block=512)
        slots = get_optimizer("onebit_adam").state_slots("replicated")

        def canon(o, nb):
            st = StateTree({k: np.asarray(v) for k, v in o.items()})
            return canonicalize_state(st, slots, ctx, n_buckets=nb,
                                      block=512)

        ref_c = canon(ref_o, 1)
        for pipe, nb in ((3, 3), (4, 4)):
            p, o, h = outs[pipe]
            for a, b in zip(jax.tree.leaves(ref_p),
                            jax.tree.leaves(p)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            for name in ("m", "v", "worker_err"):
                np.testing.assert_array_equal(np.asarray(ref_o[name]),
                                              np.asarray(o[name]))
            assert [r["loss"] for r in ref_h] == \
                [r["loss"] for r in h]
            c = canon(o, nb)
            for name in (s.name for s in slots if s.bucket_keyed):
                np.testing.assert_array_equal(np.asarray(ref_c[name]),
                                              np.asarray(c[name]))
            print("OK resume bitwise pipeline=", pipe)
        """, n=4, timeout=1800)
        assert out.count("OK") == 2
