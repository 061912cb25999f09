"""The HLO roofline analyzer: parser units + scanned/unrolled parity."""
import jax
import jax.numpy as jnp

from repro.roofline.hlo_analysis import (analyze_hlo, parse_def, shape_bytes,
                                         shape_dims)


def test_shape_parsing():
    assert shape_bytes("f32[16,64]{1,0}") == 16 * 64 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], s32[2,2])") == 16 + 16
    assert shape_dims("bf16[8,128]{1,0}") == [8, 128]
    assert shape_bytes("pred[7]") == 7


def test_parse_def_tuple_type_with_comments():
    line = ('  %while.53 = (s32[], bf16[16,4096,2048]{2,1,0}, '
            '/*index=5*/f32[36]{0}) while(%tuple.4), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"36"}}')
    d = parse_def(line)
    assert d.opcode == "while"
    assert shape_bytes(d.type_str) == 4 + 16*4096*2048*2 + 36*4


def test_scanned_equals_unrolled_flops():
    D, F, L = 32, 64, 7

    def layer(x, w):
        return jnp.tanh(x @ w[0]) @ w[1]

    def scanned(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (layer(c, w), None), x, ws)
        return y.sum()

    def unrolled(x, ws):
        for i in range(L):
            x = layer(x, (ws[0][i], ws[1][i]))
        return x.sum()

    x = jax.ShapeDtypeStruct((16, D), jnp.float32)
    ws = (jax.ShapeDtypeStruct((L, D, F), jnp.float32),
          jax.ShapeDtypeStruct((L, F, D), jnp.float32))
    cs = analyze_hlo(jax.jit(scanned).lower(x, ws).compile().as_text())
    cu = analyze_hlo(jax.jit(unrolled).lower(x, ws).compile().as_text())
    expect = 2 * 16 * D * F * 2 * L
    assert cs.flops == expect, (cs.flops, expect)
    assert cu.flops == expect


def test_dus_counts_slice_not_buffer():
    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (0, 0))

    buf = jax.ShapeDtypeStruct((4096, 512), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 512), jnp.float32)
    c = analyze_hlo(jax.jit(f, donate_argnums=0).lower(buf, upd).compile().as_text())
    # traffic must be ~2x the update slice, nowhere near the 8 MiB buffer
    assert c.hbm_bytes <= 4 * 512 * 4 * 2 + 1024, c.hbm_bytes
