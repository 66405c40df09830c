"""Pallas fused GF-GEMM kernel tests (interpret mode under the CPU mesh —
the identical kernel code compiles for real TPU via Mosaic)."""

import jax.numpy as jnp
import numpy as np
import pytest

from gpu_rscode_tpu.ops.gemm import gf_matmul
from gpu_rscode_tpu.ops.gf import get_field
from gpu_rscode_tpu.ops.pallas_gemm import gf_matmul_pallas


@pytest.mark.parametrize(
    "p,k,m",
    [(2, 4, 256), (4, 10, 5000), (1, 1, 128), (8, 32, 1024), (3, 5, 100)],
)
def test_pallas_vs_oracle(p, k, m):
    gf = get_field(8)
    rng = np.random.default_rng(p + k + m)
    A = rng.integers(0, 256, size=(p, k), dtype=np.uint8)
    B = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_ragged_tile_edge():
    """m smaller than, equal to, and one over the tile size."""
    gf = get_field(8)
    rng = np.random.default_rng(9)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    for m in (64, 2048, 2049, 4097):
        B = rng.integers(0, 256, size=(4, m), dtype=np.uint8)
        got = np.asarray(gf_matmul_pallas(A, B, tile=2048))
        np.testing.assert_array_equal(got, gf.matmul(A, B))


@pytest.mark.parametrize("acc_dtype", [jnp.bfloat16, jnp.float32, jnp.int8])
def test_pallas_acc_dtypes(acc_dtype):
    gf = get_field(8)
    rng = np.random.default_rng(11)
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 777), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B, acc_dtype=acc_dtype))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_via_strategy_dispatch():
    gf = get_field(8)
    rng = np.random.default_rng(12)
    A = rng.integers(0, 256, size=(3, 6), dtype=np.uint8)
    B = rng.integers(0, 256, size=(6, 300), dtype=np.uint8)
    got = np.asarray(gf_matmul(A, B, strategy="pallas"))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_file_roundtrip(tmp_path):
    from gpu_rscode_tpu import api
    from gpu_rscode_tpu.tools.make_conf import make_conf

    path = str(tmp_path / "f.bin")
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, size=20_000, dtype=np.uint8).tobytes()
    open(path, "wb").write(data)
    api.encode_file(path, 4, 2, strategy="pallas")
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out, strategy="pallas")
    assert open(out, "rb").read() == data


@pytest.mark.parametrize(
    "expand",
    ["shift", "shift_raw", "sign", "nibble",
     "packed32", "sign16", "shift_u8", "nibble_const", "nibble32",
     "pack2"],  # r4 set
)
def test_pallas_expand_modes(expand):
    """All data-expansion formulations are bit-exact (the sign trick's
    {0,-1} planes preserve accumulator parity; the nibble one-hots select
    columns of the (p*w, k*32) operator)."""
    gf = get_field(8)
    rng = np.random.default_rng(21)
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 1000), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B, expand=expand))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_nibble_rejects_wide_field():
    """The nibble strategy is GF(2^8)-specific: two one-hot nibbles per byte."""
    rng = np.random.default_rng(24)
    A = rng.integers(0, 1 << 16, size=(2, 3), dtype=np.uint16)
    B = rng.integers(0, 1 << 16, size=(3, 256), dtype=np.uint16)
    with pytest.raises(ValueError, match="nibble"):
        gf_matmul_pallas(A, B, w=16, expand="nibble")


@pytest.mark.parametrize(
    "expand",
    ["shift", "shift_raw", "sign", "nibble",
     "packed32", "sign16", "shift_u8", "nibble_const", "nibble32"],
)
def test_pallas_preparity_expand_modes(expand):
    """fold_parity=False (the stripe-sharded pre-psum form) under every
    expansion: folding the raw accumulators must equal the oracle."""
    from gpu_rscode_tpu.ops.gemm import from_bitplanes

    gf = get_field(8)
    rng = np.random.default_rng(25)
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 640), dtype=np.uint8)
    acc = gf_matmul_pallas(A, B, expand=expand, fold_parity=False)
    assert acc.shape == (4 * 8, 640)
    got = np.asarray(from_bitplanes(acc, 8))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


@pytest.mark.parametrize("expand", ["shift", "sign"])
def test_pallas_wide_symbols(expand):
    """GF(2^16) through the fused kernel (uint16 lanes, 16 planes)."""
    gf = get_field(16)
    rng = np.random.default_rng(22)
    A = rng.integers(0, 1 << 16, size=(3, 5), dtype=np.uint16)
    B = rng.integers(0, 1 << 16, size=(5, 600), dtype=np.uint16)
    got = np.asarray(gf_matmul_pallas(A, B, w=16, expand=expand))
    assert got.dtype == np.uint16
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_shift_raw_wide_symbols():
    """shift_raw at w=16: int8 acc is exact (mod-256 wrap is parity-safe);
    bf16 acc is rejected (65535 exceeds bf16's exact-integer range)."""
    import jax.numpy as jnp

    gf = get_field(16)
    rng = np.random.default_rng(26)
    A = rng.integers(0, 1 << 16, size=(3, 5), dtype=np.uint16)
    B = rng.integers(0, 1 << 16, size=(5, 600), dtype=np.uint16)
    got = np.asarray(
        gf_matmul_pallas(A, B, w=16, expand="shift_raw", acc_dtype=jnp.int8)
    )
    np.testing.assert_array_equal(got, gf.matmul(A, B))
    with pytest.raises(ValueError, match="shift_raw"):
        gf_matmul_pallas(A, B, w=16, expand="shift_raw",
                         acc_dtype=jnp.bfloat16)


@pytest.mark.parametrize("expand", ["shift", "shift_raw", "sign", "nibble"])
def test_pallas_sign_int8_acc(expand):
    """int8 accumulation path (the TPU default) under both expansions."""
    import jax.numpy as jnp

    gf = get_field(8)
    rng = np.random.default_rng(23)
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 512), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B, acc_dtype=jnp.int8, expand=expand))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


@pytest.mark.parametrize("expand", ["shift", "shift_raw"])
@pytest.mark.parametrize("w", [4, 8, 16])
def test_pallas_dot_refold(expand, w):
    """refold='dot' (MXU parity refold via the (p, p*w) bit-weight
    operator) is bit-exact at every legacy width w in {4, 8, 16}
    (gf.h's field set); powers of two are exact in bf16 and the folded
    values stay below 2^24 in f32."""
    import jax.numpy as jnp

    gf = get_field(w)
    dt = np.uint8 if w <= 8 else np.uint16
    rng = np.random.default_rng(29)
    A = rng.integers(0, 1 << w, size=(4, 6), dtype=dt)
    B = rng.integers(0, 1 << w, size=(6, 640), dtype=dt)
    kw = {"acc_dtype": jnp.int8} if (w == 16 and expand == "shift_raw") else {}
    got = np.asarray(
        gf_matmul_pallas(A, B, w=w, expand=expand, refold="dot", **kw)
    )
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_pack2():
    """pack2 (two bytes per int32 lane through an outside-the-kernel u16
    bitcast): odd column counts pad/slice, the depth bound k*w < 256 and
    the pre-parity form are rejected, and the env fallback downgrades."""
    gf = get_field(8)
    rng = np.random.default_rng(31)
    for m in (511, 512, 4097):  # odd, even, tile-overhang odd
        A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
        B = rng.integers(0, 256, size=(10, m), dtype=np.uint8)
        got = np.asarray(gf_matmul_pallas(A, B, expand="pack2", tile=2048))
        np.testing.assert_array_equal(got, gf.matmul(A, B))
    # tile=384 is 128-aligned but its pack2 halving (192) is not; the
    # consumption clamp must re-align it (the silent-demotion guard).
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 4097), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B, expand="pack2", tile=384))
    np.testing.assert_array_equal(got, gf.matmul(A, B))
    A, B = (rng.integers(0, 256, size=(4, 10), dtype=np.uint8),
            rng.integers(0, 256, size=(10, 256), dtype=np.uint8))
    with pytest.raises(ValueError, match="pre-parity"):
        gf_matmul_pallas(A, B, expand="pack2", fold_parity=False)


@pytest.mark.parametrize("k", [31, 32, 63, 128])
def test_pallas_pack2_split_k(k):
    """Deep contractions (k*w >= 256) run pack2 as carry-free depth-248
    slices XORed together — exact because XOR is the field addition."""
    gf = get_field(8)
    rng = np.random.default_rng(33)
    A = rng.integers(0, 256, size=(4, k), dtype=np.uint8)
    B = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
    got = np.asarray(gf_matmul_pallas(A, B, expand="pack2"))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def test_pallas_pack2_env_fallback(monkeypatch):
    """RS_PALLAS_EXPAND=pack2 on an inapplicable call (the pre-parity
    stripe form) warns and falls back instead of crashing production."""
    from gpu_rscode_tpu.ops.gemm import from_bitplanes

    gf = get_field(8)
    rng = np.random.default_rng(32)
    A = rng.integers(0, 256, size=(4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, size=(10, 256), dtype=np.uint8)
    monkeypatch.setenv("RS_PALLAS_EXPAND", "pack2")
    with pytest.warns(UserWarning, match="does not apply"):
        acc = gf_matmul_pallas(A, B, fold_parity=False)
    got = np.asarray(from_bitplanes(acc, 8))
    np.testing.assert_array_equal(got, gf.matmul(A, B))


def _spy_matmul(monkeypatch, seen, force_interpret=False):
    """Route _pallas_matmul through a recording spy (one shared signature
    to maintain when the kernel entry grows a parameter)."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    real = pg._pallas_matmul

    def spy(A, B, w, tile, acc_dtype, interpret, expand, fold=True,
            refold="sum"):
        seen.append(dict(w=w, tile=tile, acc_dtype=acc_dtype,
                         expand=expand, refold=refold))
        return real(A, B, w, tile, acc_dtype,
                    True if force_interpret else interpret,
                    expand, fold, refold)

    monkeypatch.setattr(pg, "_pallas_matmul", spy)


def test_refold_env_override(monkeypatch):
    """RS_PALLAS_REFOLD routes the default refold for whole-pipeline
    experiments; unknown values warn and fall back to the production
    default 'dot' (an env typo must not silently switch the run off the
    default formulation)."""
    seen = []
    _spy_matmul(monkeypatch, seen)
    gf = get_field(8)
    rng = np.random.default_rng(30)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    want = gf.matmul(A, B)
    monkeypatch.setenv("RS_PALLAS_REFOLD", "sum")
    np.testing.assert_array_equal(np.asarray(gf_matmul_pallas(A, B)), want)
    assert seen[-1]["refold"] == "sum"
    monkeypatch.setenv("RS_PALLAS_REFOLD", "bogus")
    with pytest.warns(UserWarning, match="RS_PALLAS_REFOLD"):
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(A, B)), want
        )
    assert seen[-1]["refold"] == "dot"


def test_refold_default_per_width(monkeypatch):
    """The refold default is 'dot' at w=8 (wins every probed shape) and
    'sum' at w=16 — w16+dot measured BIMODAL at fixed shape (82-148 GB/s
    vs sum's stable ~102, w16_cross_*_tpu_20260801T*), so the stable
    refold ships and dot stays opt-in via RS_PALLAS_REFOLD."""
    seen = []
    _spy_matmul(monkeypatch, seen)
    rng = np.random.default_rng(31)
    for w, want_refold in ((8, "dot"), (16, "sum")):
        gf = get_field(w)
        hi = 256 if w == 8 else 65536
        dt = np.uint8 if w == 8 else np.uint16
        A = rng.integers(0, hi, size=(2, 4)).astype(dt)
        B = rng.integers(0, hi, size=(4, 512)).astype(dt)
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(A, B, w=w)), gf.matmul(A, B)
        )
        assert seen[-1]["refold"] == want_refold, (w, seen[-1])


def _fake_timer(monkeypatch, results):
    """Replace _time_refold with a deterministic fake.  _autotune_refold
    times candidates in the fixed order ("sum", "dot"), so `results` is
    consumed positionally; an Exception instance raises instead (a
    lowering failure surfaces during the warm-up call inside the real
    timer).  Returns the call log."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    calls = []

    def fake(run):
        calls.append(run)
        r = results[len(calls) - 1]
        if isinstance(r, Exception):
            raise r
        return r

    monkeypatch.setattr(pg, "_time_refold", fake)
    monkeypatch.setattr(pg, "_AUTOTUNE_CACHE", {})
    return calls


def test_refold_autotune_decision(monkeypatch):
    """refold='autotune' ships the dot refold only on a real measured win
    (dot < margin * sum — ties and jitter go to the stable 'sum'), and the
    decision is cached per shape class so only the first dispatch pays the
    calibration.  Motivation: the w16 dot mode is a compile-time coin flip
    (w16_bimodal_t*_tpu_20260801T*), so no static default can ship its
    fast mode — a per-process calibration can."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    rng = np.random.default_rng(33)
    A = rng.integers(0, 65536, size=(2, 4)).astype(np.uint16)
    B = rng.integers(0, 65536, size=(4, 512)).astype(np.uint16)
    gf = get_field(16)
    want = gf.matmul(A, B)

    # Fast-dot compile: dot well under margin*sum -> dot ships.
    calls = _fake_timer(monkeypatch, [1.0, 0.5])
    seen = []
    _spy_matmul(monkeypatch, seen)
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A, B, w=16, refold="autotune")), want
    )
    assert seen[-1]["refold"] == "dot"
    assert len(calls) == 2
    # Cached: the second identical dispatch does not re-time.
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A, B, w=16, refold="autotune")), want
    )
    assert len(calls) == 2 and seen[-1]["refold"] == "dot"

    # Slow-dot compile (within margin of sum): the stable refold ships.
    calls = _fake_timer(monkeypatch, [1.0, 0.95])
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A, B, w=16, refold="autotune")), want
    )
    assert seen[-1]["refold"] == "sum" and len(calls) == 2

    # A variant that fails to compile (a backend error) or any other
    # failure propagates: it is never cached as a 'sum' win.
    import jax

    for err in (jax.errors.JaxRuntimeError("mosaic refused"),
                ValueError("shape bug")):
        calls = _fake_timer(monkeypatch, [1.0, err])
        with pytest.raises(type(err)):
            gf_matmul_pallas(A, B, w=16, refold="autotune")
        assert not pg.autotune_decisions()  # nothing cached over it


def test_refold_autotune_env_and_preparity(monkeypatch):
    """RS_PALLAS_REFOLD=autotune routes the default resolution into the
    calibrator; the pre-parity (fold_parity=False) form has no refold
    stage, so autotune resolves to the per-width static default without
    timing anything."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg
    from gpu_rscode_tpu.ops.gemm import from_bitplanes

    rng = np.random.default_rng(34)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    gf = get_field(8)
    want = gf.matmul(A, B)

    calls = _fake_timer(monkeypatch, [1.0, 0.5])
    seen = []
    _spy_matmul(monkeypatch, seen)
    monkeypatch.setenv("RS_PALLAS_REFOLD", "autotune")
    np.testing.assert_array_equal(np.asarray(gf_matmul_pallas(A, B)), want)
    assert seen[-1]["refold"] == "dot" and len(calls) == 2

    acc = gf_matmul_pallas(A, B, fold_parity=False, refold="autotune")
    np.testing.assert_array_equal(np.asarray(from_bitplanes(acc, 8)), want)
    # No new timing calls; the pre-parity dispatch used the w=8 default.
    assert len(calls) == 2 and seen[-1]["refold"] == "dot"


def test_refold_autotune_under_jit_trace(monkeypatch):
    """Inside a caller's jit trace the operands are tracers and
    block_until_ready is a no-op — "timing" there would measure trace
    overhead and cache a garbage decision for every later eager call of
    the shape.  Autotune must refuse to calibrate under a trace: warn,
    use the static per-width default, time nothing, cache nothing."""
    import jax

    from gpu_rscode_tpu.ops import pallas_gemm as pg

    calls = _fake_timer(monkeypatch, [1.0, 0.5])
    seen = []
    _spy_matmul(monkeypatch, seen, force_interpret=True)
    rng = np.random.default_rng(36)
    A = rng.integers(0, 65536, size=(2, 4)).astype(np.uint16)
    B = rng.integers(0, 65536, size=(4, 512)).astype(np.uint16)
    gf = get_field(16)

    jitted = jax.jit(
        lambda a, b: gf_matmul_pallas(a, b, w=16, refold="autotune")
    )
    with pytest.warns(UserWarning, match="cannot calibrate"):
        got = np.asarray(jitted(A, B))
    np.testing.assert_array_equal(got, gf.matmul(A, B))
    assert seen[-1]["refold"] == "sum"  # static w=16 default, not "dot"
    assert not calls and not pg._AUTOTUNE_CACHE


def test_refold_autotune_real_calibration():
    """End-to-end (no fakes): a real timed calibration in interpret mode
    picks one of the two variants and the output is bit-exact either way
    — correctness must not depend on which mode wins the race."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    pg._AUTOTUNE_CACHE.clear()
    rng = np.random.default_rng(35)
    for w in (8, 16):
        gf = get_field(w)
        hi = 256 if w == 8 else 65536
        dt = np.uint8 if w == 8 else np.uint16
        A = rng.integers(0, hi, size=(2, 4)).astype(dt)
        B = rng.integers(0, hi, size=(4, 512)).astype(dt)
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(A, B, w=w, refold="autotune")),
            gf.matmul(A, B),
        )
    pg._AUTOTUNE_CACHE.clear()


def test_tile_env_override(monkeypatch):
    """RS_PALLAS_TILE sets the kernel column tile (the true analog of the
    reference's -p gridDim.x cap — the CLI's -p sizes segments instead);
    non-positive-int values warn and fall back to the measured default,
    and an explicit tile argument always wins over the env."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    seen = []
    _spy_matmul(monkeypatch, seen)
    gf = get_field(8)
    rng = np.random.default_rng(33)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(4, 2048), dtype=np.uint8)
    want = gf.matmul(A, B)
    monkeypatch.setenv("RS_PALLAS_TILE", "256")
    np.testing.assert_array_equal(np.asarray(gf_matmul_pallas(A, B)), want)
    assert seen[-1]["tile"] == 256
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A, B, tile=512)), want
    )
    assert seen[-1]["tile"] == 512  # explicit argument beats the env
    monkeypatch.setenv("RS_PALLAS_TILE", "200")
    with pytest.warns(UserWarning, match="128-lane"):
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(A, B)), want
        )
    assert seen[-1]["tile"] == 256  # misaligned env tile rounds up
    monkeypatch.setenv("RS_PALLAS_TILE", "zero")
    with pytest.warns(UserWarning, match="RS_PALLAS_TILE"):
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(A, B)), want
        )
    assert seen[-1]["tile"] == pg.DEFAULT_TILE  # interpret-mode default


def test_production_defaults(monkeypatch):
    """The measured production defaults (expand_r4b_*/expand_r4c_*
    captures): expand='shift_raw' + refold='dot' at w=8; w=16 keeps
    refold='sum' (its only dot hardware attempt never completed), and an
    explicit non-int8 acc_dtype there silently selects the masked
    'shift' formulation (shift_raw would need int8, which the caller
    overrode)."""
    seen = []
    _spy_matmul(monkeypatch, seen)
    monkeypatch.delenv("RS_PALLAS_EXPAND", raising=False)
    monkeypatch.delenv("RS_PALLAS_REFOLD", raising=False)
    gf = get_field(8)
    rng = np.random.default_rng(31)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A, B)), gf.matmul(A, B)
    )
    assert seen[-1]["expand"] == "shift_raw"
    assert seen[-1]["refold"] == "dot"
    gf16 = get_field(16)
    A16 = rng.integers(0, 1 << 16, size=(2, 4), dtype=np.uint16)
    B16 = rng.integers(0, 1 << 16, size=(4, 512), dtype=np.uint16)
    want16 = gf16.matmul(A16, B16)
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_pallas(A16, B16, w=16)), want16
    )
    assert seen[-1]["expand"] == "shift_raw"
    assert seen[-1]["acc_dtype"] == jnp.int8
    assert seen[-1]["refold"] == "sum"
    np.testing.assert_array_equal(
        np.asarray(
            gf_matmul_pallas(A16, B16, w=16, acc_dtype=jnp.bfloat16)
        ),
        want16,
    )
    assert seen[-1]["expand"] == "shift"


def test_uniform_tpu_defaults_match_committed_capture():
    """Evidence lock: the int8-at-every-depth default must agree with the
    committed post-flip k-sweep it cites
    (bench_captures/k_sweep_postflip_tpu_20260801T002730Z.jsonl): at every
    swept k, the best int8 cell must beat the best bf16 cell, and the
    shipped TPU_TILE must be within 10 % of that k's best tile — so the
    defaults cannot drift from the capture without re-measurement."""
    import json
    import pathlib
    import re

    from gpu_rscode_tpu.ops import pallas_gemm as pg

    cap = (
        pathlib.Path(__file__).resolve().parents[1]
        / "bench_captures"
        / "k_sweep_postflip_tpu_20260801T002730Z.jsonl"
    )
    cells: dict[int, dict[tuple[str, int], float]] = {}
    pat = re.compile(r"k(\d+)_acc-(int8|bf16)@(\d+)")
    for line in cap.read_text().splitlines():
        if not line.startswith("{"):
            continue
        for key, val in json.loads(line).items():
            m = pat.fullmatch(key)
            if m and isinstance(val, float):
                cells.setdefault(int(m.group(1)), {})[
                    (m.group(2), int(m.group(3)))
                ] = val
    assert set(cells) == {4, 10, 32, 64, 128}
    for k, grid in cells.items():
        best_int8 = max(v for (a, _), v in grid.items() if a == "int8")
        best_bf16 = max(v for (a, _), v in grid.items() if a == "bf16")
        assert best_int8 > best_bf16, (k, grid)
        int8_at_default = grid.get(("int8", pg.TPU_TILE))
        assert int8_at_default is not None, (k, pg.TPU_TILE)
        assert int8_at_default >= 0.90 * best_int8, (k, grid)


def test_uniform_tpu_defaults(monkeypatch):
    """On a TPU backend the tile/acc default is int8@TPU_TILE at EVERY
    contraction depth — the post-flip k-sweep (committed capture
    k_sweep_postflip_tpu_20260801T002730Z.jsonl) retired the earlier
    bf16@32768 deep split.  Spied at the _pallas_matmul boundary with a
    faked TPU presence — every combination is bit-exact, so output
    equality cannot prove which default was chosen."""
    import jax.numpy as jnp

    from gpu_rscode_tpu.ops import pallas_gemm as pg

    seen = []
    # Run in interpret mode regardless (no real TPU under the test mesh)
    _spy_matmul(monkeypatch, seen, force_interpret=True)
    monkeypatch.setattr(
        "gpu_rscode_tpu.utils.backend.tpu_devices_present", lambda: True
    )
    gf = get_field(8)
    rng = np.random.default_rng(27)
    # int8@TPU_TILE at every depth: the post-flip k-sweep
    # (k_sweep_postflip_tpu_20260801T*) retired the bf16 deep split.
    for k, want_tile, want_acc in [
        (10, pg.TPU_TILE, jnp.int8),          # depth 80
        (32, pg.TPU_TILE, jnp.int8),          # depth 256
        (64, pg.TPU_TILE, jnp.int8),          # depth 512
    ]:
        A = rng.integers(0, 256, size=(4, k), dtype=np.uint8)
        B = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
        got = np.asarray(gf_matmul_pallas(A, B))
        np.testing.assert_array_equal(got, gf.matmul(A, B))
        last = seen[-1]
        assert (last["tile"], last["acc_dtype"]) == (want_tile, want_acc), \
            (k, last)


def test_expand_env_default(monkeypatch):
    """RS_PALLAS_EXPAND overrides the default formulation for whole-pipeline
    experiments; unknown/inapplicable values warn and fall back to the
    production default that applies (shift_raw; shift at w=16 with an
    explicit non-int8 acc), and an explicit expand= argument always wins.
    The formulation actually reaching the kernel is spied on — every
    expansion is bit-identical, so output equality alone cannot prove the
    env var was honored."""
    seen = []
    _spy_matmul(monkeypatch, seen)
    rng = np.random.default_rng(3)
    A = rng.integers(0, 256, size=(2, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    want = get_field(8).matmul(A, B)
    monkeypatch.setenv("RS_PALLAS_EXPAND", "packed32")
    got = np.asarray(gf_matmul_pallas(A, B))  # env default applies (w=8)
    np.testing.assert_array_equal(got, want)
    assert seen[-1]["expand"] == "packed32"
    # w=16 cannot run a byte-granular strategy: env warns, falls back.
    A16 = rng.integers(0, 1 << 16, size=(2, 4), dtype=np.uint16)
    B16 = rng.integers(0, 1 << 16, size=(4, 512), dtype=np.uint16)
    want16 = get_field(16).matmul(A16, B16)
    with pytest.warns(UserWarning, match="does not apply"):
        got16 = np.asarray(gf_matmul_pallas(A16, B16, w=16))
    np.testing.assert_array_equal(got16, want16)
    assert seen[-1]["expand"] == "shift_raw"
    # an env typo warns and falls back instead of crashing production
    monkeypatch.setenv("RS_PALLAS_EXPAND", "packed_32")
    with pytest.warns(UserWarning, match="unknown"):
        got2 = np.asarray(gf_matmul_pallas(A, B))
    np.testing.assert_array_equal(got2, want)
    assert seen[-1]["expand"] == "shift_raw"
    # explicit argument wins over the env var (no warning, no fallback)
    monkeypatch.setenv("RS_PALLAS_EXPAND", "nonsense")
    got3 = np.asarray(gf_matmul_pallas(A, B, expand="sign"))
    np.testing.assert_array_equal(got3, want)
    assert seen[-1]["expand"] == "sign"
