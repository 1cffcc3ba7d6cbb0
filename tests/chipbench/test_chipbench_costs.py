"""Operation and byte counts against hand counts at small shapes."""
from chipbench import costs
from chipbench.reference.qwen2 import dims

PEAKS = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
M = dims({"hidden_size": 4, "intermediate_size": 6, "num_hidden_layers": 2,
          "num_attention_heads": 2, "num_key_value_heads": 1,
          "vocab_size": 10, "rope_theta": 1e4, "rms_norm_eps": 1e-5})


def test_least_seconds_names_its_bound():
    assert costs.least_seconds(1000, 10, PEAKS) == (10.0, "operations")
    assert costs.least_seconds(10, 1000, PEAKS) == (100.0, "bytes")


def test_env_step_bytes_by_hand():
    # E=1, K=1, A=3, l=1: read 2+4+6 state, 7 constants, 3 action, 2+1
    # queue = 25; write 12 state, 3 queue, 3*(1+1) obs, 2 = 23
    assert costs.env_step_bytes(1, 1, 1, 3, 1) == 4 * (25 + 23)
    assert costs.env_step_bytes(5, 1, 1, 3, 1) == 5 * 4 * 48


def test_actor_ops_by_hand():
    # rows=1, cols=2, A=1, d_attn=1, hidden=1, t_dim=0, T=1:
    # encoder 2*2*1*1*3 + 2*2*2*2*1 + 2*2*1 = 12 + 16 + 4; MLP 2*(3+1+1)=10;
    # head 2
    assert costs.actor_ops(1, 2, 1, 1, 1, 0, 1) == 32 + 10 + 2


def test_qwen2_counts_by_hand():
    assert (M["hd"], M["Vp"]) == (2, 256)
    mats = 4 * (2 + 2) * 2 + 2 * 2 * 4 + 3 * 4 * 6           # 32+16+72
    params = mats + (2 + 2) * 2 + 2 * 4                      # + biases, norms
    wbytes = 2 * (2 * params + 4 + 256 * 4)
    assert costs.weight_bytes(M) == wbytes
    ops, nbytes = costs.prefill_cost(M, 2, 6)
    attn = 2 * 2 * 2 * 2 * 2 * 3 * 4 // 2                   # 2 chunks of 3
    assert ops == 2 * (2 * 6 * mats + attn) + 2 * 4 * 10
    assert nbytes == wbytes + 2 * 6 * 2 * 1 * 2 * 2 + 4 * 6
    ops, nbytes = costs.decode_cost(M, 6)
    assert ops == 2 * (2 * mats + 2 * 2 * 2 * 2 * 7) + 2 * 4 * 10
    assert nbytes == wbytes + 2 * 8 * 2 * 1 * 2 * 2


def test_sac_update_ops_by_hand():
    # critic over obs_dim=2, A=1, hidden=1: 2*((2+1)*1 + 1 + 1) = 10
    assert costs.critic_ops(2, 1, 1) == 10
    # actor (rows=1, cols=2, A=1, d_attn=1, hidden=1, t_dim=0, T=1) 44;
    # target 44 + 20, critics 6*10, actor loss 3*44 + 4*10; batch 3
    assert costs.sac_update_ops(3, 1, 2, 1, 1, 1, 0, 1, 1) == 3 * (
        64 + 60 + 172)
