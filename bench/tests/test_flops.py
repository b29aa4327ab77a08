"""The FLOP and byte formulas against counts made by hand."""
import flops
import pytest
import spec


def _model(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")["model"]


def test_musicgen_hand_counts():
    m = _model("musicgen-medium")
    # per layer: q,k,v,o 4 * 1536^2 plus a 2-matrix GELU MLP 2 * 1536 * 6144
    per_layer = 2 * (4 * 1536 * 1536 + 2 * 1536 * 6144)
    assert flops.matmul_flops_per_token(m) == 48 * per_layer
    # a token at position 99 attends to 100 keys: 4 * 24 * 64 * 100 / layer
    assert flops.decode_flops(m, 99) == (48 * per_layer
                                         + 48 * 4 * 24 * 64 * 100
                                         + 2 * 1536 * 2048)
    f, b = flops.paged_attn_cost(m, 99)
    assert f == 48 * 4 * 1536 * 100
    # q bf16 + new K,V bf16 read + 99 cached K,V + new K,V written + ctx f32
    assert b == 48 * (2 * 1536 + 4 * 1536 + 4 * 1536 * 99 + 4 * 1536
                      + 4 * 1536)
    assert flops.kv_bytes_per_token(m) == 288 * 1024


def test_internvl_hand_counts():
    m = _model("internvl2-2b")
    # GQA: q,o 2048 x 2048, k,v 2048 x 1024; SwiGLU 3 * 2048 * 8192
    per_layer = 2 * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
    assert flops.matmul_flops_per_token(m) == 24 * per_layer
    # a 3-token prompt: keys 1 + 2 + 3 = 6, one LM-head row
    assert flops.prefill_flops(m, 3) == (3 * 24 * per_layer
                                         + 24 * 4 * 2048 * 6
                                         + 2 * 2048 * 92553)
    f, b = flops.paged_attn_cost(m, 10)
    assert f == 24 * 4 * 2048 * 11
    assert b == 24 * (2 * 2048 + 4 * 1024 + 4 * 1024 * 10 + 4 * 1024
                      + 4 * 2048)
    assert flops.kv_bytes_per_token(m) == 96 * 1024


@pytest.mark.parametrize("name", ["musicgen-medium", "internvl2-2b"])
def test_chunk_is_sum_of_steps(name):
    m = _model(name)
    assert flops.chunk_decode_flops(m, [5, 40], 2) == sum(
        flops.decode_flops(m, p) for p in (5, 6, 40, 41))
