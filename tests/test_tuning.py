"""Shape-keyed block-size tuning table: heuristics, cache, validation."""

import json

import pytest

from repro.kernels import tuning


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    tuning.clear()
    yield
    tuning.clear()


class TestHeuristics:
    def test_matmul_defaults_divide(self):
        for shape in [(256, 1024, 1024), (8, 128, 128), (96, 384, 192),
                      (1, 64, 64)]:
            cfg = tuning.get_block_config("ent_matmul", shape)
            m, k, n = shape
            assert m % cfg["block_m"] == 0
            assert k % cfg["block_k"] == 0
            assert n % cfg["block_n"] == 0

    def test_decode_skinny_m(self):
        cfg = tuning.get_block_config("int8_matmul", (8, 4096, 4096))
        assert cfg["block_m"] == 8

    def test_attention_defaults_divide(self):
        cfg = tuning.get_block_config("flash_attention", (256, 384, 64))
        assert 256 % cfg["block_q"] == 0 and 384 % cfg["block_kv"] == 0

    def test_unknown_family_raises(self):
        with pytest.raises(KeyError):
            tuning.get_block_config("conv3d", (1, 2, 3))


class TestPagedAttention:
    """The paged decode family: whole-page kv blocks for the kernel, page
    groups for the oracle."""

    @pytest.mark.parametrize("page,ppb", [(16, 8), (8, 16), (32, 4),
                                          (128, 1), (3, 43)])
    def test_heuristic_128_tokens_a_block(self, page, ppb):
        cfg = tuning.get_block_config("paged_attention", (page, 128))
        assert cfg == {"pages_per_block": ppb, "block_pages": 64}
        assert (ppb - 1) * page < 128 <= ppb * page

    @pytest.mark.parametrize("knob", ["kernel", "oracle", "both"])
    def test_candidates(self, knob):
        cands = tuning.paged_attention_candidates(16, knob=knob)
        ppbs = {c["pages_per_block"] for c in cands}
        bps = {c["block_pages"] for c in cands}
        assert all(set(c) == {"pages_per_block", "block_pages"}
                   for c in cands)
        assert len({tuple(sorted(c.items())) for c in cands}) == len(cands)
        if knob == "oracle":
            assert ppbs == {8} and bps == {8, 16, 32, 64, 128}
        else:   # whole pages, one up to 512 tokens, the heuristic's among
            assert ppbs == {1, 2, 4, 8, 16, 32}
            assert bps == ({64} if knob == "kernel"
                           else {8, 16, 32, 64, 128})

    def test_stale_block_kv_entry_rejected(self):
        """A within-page ``block_kv`` left in the persistent cache by the
        per-page kernel is never handed to the block kernel."""
        tuning.record("paged_attention", (16, 128),
                      {"block_kv": 16, "block_pages": 32})
        cfg = tuning.get_block_config("paged_attention", (16, 128))
        assert cfg == {"pages_per_block": 8, "block_pages": 64}

    @pytest.mark.parametrize("ppb", [0, -2, 2.5, "8", True, None])
    def test_bad_pages_per_block_rejected(self, ppb):
        tuning.record("paged_attention", (16, 128),
                      {"pages_per_block": ppb, "block_pages": 32})
        assert tuning.get_block_config(
            "paged_attention", (16, 128))["pages_per_block"] == 8

    def test_tuned_entry_served(self):
        tuning.record("paged_attention", (16, 128),
                      {"pages_per_block": 16, "block_pages": 32})
        assert tuning.get_block_config("paged_attention", (16, 128)) == {
            "pages_per_block": 16, "block_pages": 32}


class TestTableAndCache:
    def test_record_then_lookup_same_bucket(self):
        tuning.record("ent_matmul", (256, 1024, 1024),
                      {"block_m": 64, "block_n": 256, "block_k": 1024})
        cfg = tuning.get_block_config("ent_matmul", (256, 1024, 1024))
        assert cfg == {"block_m": 64, "block_n": 256, "block_k": 1024}
        # bucketing: 200 rounds up to the 256 bucket
        cfg2 = tuning.get_block_config("ent_matmul", (200, 1024, 1024))
        # 200 % 64 != 0 -> cached entry is invalid for this launch, falls
        # back to a divisibility-safe heuristic
        assert 200 % cfg2["block_m"] == 0

    def test_persisted_and_reloaded(self):
        tuning.record("int8_matmul", (128, 512, 512),
                      {"block_m": 128, "block_n": 128, "block_k": 256})
        with open(tuning.cache_path()) as f:
            data = json.load(f)
        assert tuning._key("int8_matmul", (128, 512, 512)) in data
        tuning.clear()
        tuning._LOADED = False  # force reload from disk
        cfg = tuning.get_block_config("int8_matmul", (128, 512, 512))
        assert cfg["block_k"] == 256

    def test_overrides_win(self):
        cfg = tuning.get_block_config("ent_matmul", (256, 1024, 1024),
                                      {"block_k": 128, "block_m": None})
        assert cfg["block_k"] == 128
        assert cfg["block_m"] == 128  # None override ignored -> heuristic


class TestDeviceKeying:
    def test_key_names_device_kind(self):
        import jax
        kind = jax.devices()[0].device_kind
        assert tuning._key("ent_matmul", (8, 2048, 2048)).startswith(
            f"{kind}/ent_matmul:")

    def test_other_device_entry_not_served(self, monkeypatch):
        """An entry swept on one device kind (e.g. a CPU / interpret-mode
        sweep) is never handed to a launch on another."""
        tuning.record("ent_matmul", (256, 1024, 1024),
                      {"block_m": 64, "block_n": 256, "block_k": 1024})
        monkeypatch.setattr(tuning, "_device_kind", lambda: "TPU v5 lite")
        cfg = tuning.get_block_config("ent_matmul", (256, 1024, 1024))
        assert cfg == tuning._heuristic("ent_matmul", (256, 1024, 1024))

    def test_unaligned_entry_rejected(self):
        """A cached block that divides the dims but breaks the TPU block
        rule (not a multiple of the 8x128 tile, not the full dim) is not
        served."""
        tuning.record("ent_matmul", (16, 1024, 1024),
                      {"block_m": 4, "block_n": 64, "block_k": 1024})
        cfg = tuning.get_block_config("ent_matmul", (16, 1024, 1024))
        assert cfg["block_m"] % 8 == 0 and cfg["block_n"] % 128 == 0

    @pytest.mark.parametrize("m", [1, 3, 8, 12, 40, 130])
    def test_pad_rows_aligned(self, m):
        mp = tuning.pad_rows(m)
        assert mp >= m and mp % 8 == 0 and mp - m < 8
        cfg = tuning.get_block_config("ent_matmul", (mp, 2048, 256))
        assert tuning._valid("ent_matmul", (mp, 2048, 256), cfg)


class TestCacheRobustness:
    """The persistent cache must survive concurrent writers and corrupt
    files: _save is write-temp + atomic rename, _load tolerates garbage."""

    def test_corrupt_cache_tolerated_and_overwritten(self):
        import os
        path = tuning.cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write('{"cpu/int8_matmul:128x512x512": {"block_m"')  # cut
        tuning._LOADED = False
        cfg = tuning.get_block_config("ent_matmul", (256, 1024, 1024))
        assert cfg["block_m"] == 128  # heuristic fallback, no raise
        tuning.record("ent_matmul", (256, 1024, 1024), {"block_m": 64})
        with open(path) as f:
            key = tuning._key("ent_matmul", (256, 1024, 1024))
            assert json.load(f)[key] == {"block_m": 64}

    def test_non_dict_payload_tolerated(self):
        import os
        path = tuning.cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        for payload in ("[1, 2, 3]", '"scalar"', "null"):
            with open(path, "w") as f:
                f.write(payload)
            tuning._LOADED = False
            tuning._TABLE.clear()
            cfg = tuning.get_block_config("ent_matmul", (128, 512, 512))
            assert cfg  # heuristics served, no raise

    def test_non_dict_entries_dropped(self):
        import os
        path = tuning.cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({tuning._key("ent_matmul", (128, 512, 512)): "bogus",
                       tuning._key("int8_matmul", (128, 512, 512)):
                           {"block_k": 256}}, f)
        tuning._LOADED = False
        tuning._TABLE.clear()
        assert tuning.get_block_config(
            "int8_matmul", (128, 512, 512))["block_k"] == 256
        # the bogus entry fell back to heuristics instead of crashing
        assert "block_m" in tuning.get_block_config("ent_matmul", (128, 512, 512))

    def test_save_is_atomic_no_temp_left_behind(self):
        import glob
        import os
        tuning.record("ent_matmul", (64, 256, 256), {"block_m": 64})
        d = os.path.dirname(tuning.cache_path())
        assert not glob.glob(os.path.join(d, "*.tmp"))
        with open(tuning.cache_path()) as f:
            json.load(f)  # valid, complete JSON


class TestAutotune:
    def test_picks_fastest_and_caches(self):
        calls = []

        def bench(cfg):
            calls.append(cfg["block_k"])
            if cfg["block_k"] == 512:
                return  # fastest: returns immediately
            import time
            time.sleep(0.002)

        cands = [{"block_m": 128, "block_n": 128, "block_k": bk}
                 for bk in (128, 256, 512)]
        best = tuning.autotune("ent_matmul", (128, 1024, 1024), bench, cands,
                               iters=2, warmup=1)
        assert best["block_k"] == 512
        assert tuning.get_block_config(
            "ent_matmul", (128, 1024, 1024))["block_k"] == 512

    def test_failing_candidates_disqualified(self):
        def bench(cfg):
            if cfg["block_k"] == 1024:
                raise RuntimeError("VMEM overflow")

        cands = [{"block_m": 64, "block_n": 64, "block_k": bk}
                 for bk in (1024, 256)]
        best = tuning.autotune("ent_matmul", (64, 1024, 64), bench, cands,
                               iters=1, warmup=0)
        assert best["block_k"] == 256

    def test_candidate_generators_divide(self):
        for c in tuning.matmul_candidates(96, 384, 192):
            assert 96 % c["block_m"] == 0 and 384 % c["block_k"] == 0
        for c in tuning.attention_candidates(256, 384):
            assert 256 % c["block_q"] == 0 and 384 % c["block_kv"] == 0
