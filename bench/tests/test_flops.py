"""flops.py and the families' FLOP counts against hand counts at the
cells' shapes."""
import flops
import harness

WDL_FAMILY = harness.load_module(
    harness.os.path.join(harness.BENCH, "families", "dlrm-wdl.py"),
    "family_dlrm_wdl")
LLM_FAMILY = harness.load_module(
    harness.os.path.join(harness.BENCH, "families", "llm-dense.py"),
    "family_llm_dense")

WDL = {"fields_a": 26, "fields_b": 13, "vocab": 649281, "embed_dim": 16,
       "z_dim": 256, "hidden": [512, 256]}
SMOLLM = {"num_hidden_layers": 32, "hidden_size": 960,
          "intermediate_size": 2560, "num_attention_heads": 15,
          "num_key_value_heads": 5, "head_dim": 64, "vocab_size": 49152}


def test_wdl_weights_and_round():
    # A: 416*512 + 512*256 + 256*256; B: 208*512 + 512*256 + 256*256;
    # top: 512*256 + 256*1
    a = 416 * 512 + 512 * 256 + 256 * 256
    b = 208 * 512 + 512 * 256 + 256 * 256
    top = 512 * 256 + 256
    assert flops.dlrm_wdl_weights(WDL) == a + b + top == 844032
    job = {"protocol": "celu", "R": 5, "batch": 4096}
    assert flops.round_flops(WDL_FAMILY, WDL, job) == 6 * 4096 * 6 * 844032
    job["protocol"] = "vanilla"
    assert flops.round_flops(WDL_FAMILY, WDL, job) == 4096 * 6 * 844032


def test_smollm_weights():
    d, hd = 960, 64
    layer = d * 15 * hd + 2 * d * 5 * hd + 15 * hd * d + 3 * d * 2560
    assert layer == 921600 + 614400 + 921600 + 7372800
    assert flops.llm_weights(SMOLLM) == 32 * layer + d * d + d * 49152
    job = {"protocol": "celu", "R": 5, "batch": 8, "seq_len": 512}
    per_token = 6 * flops.llm_weights(SMOLLM) + 12 * 32 * 15 * 64 * 512
    assert flops.round_flops(LLM_FAMILY, SMOLLM, job) == \
        6 * 8 * 512 * per_token
