"""``bench/scope_joined.py``: a Mosaic kernel's multi-line attribute block
joined onto its instruction's line, so that its scope is read."""
from bench import scope_joined as sj, scopes as sc

KERNEL = """HloModule jit_step, is_scheduled=true

ENTRY %main (q: bf16[2,512,64]) -> bf16[2,512,64] {
  %q = bf16[2,512,64]{2,1,0} parameter(0)
  %splash_mqa_fwd.1 = bf16[2,512,64]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(step)/repro.lm.attention/vmap(jit(_splash_attention))/pallas_call"}
  ROOT %add.2 = bf16[2,512,64]{2,1,0} add(%splash_mqa_fwd.1, %q), metadata={op_name="jit(step)/repro.lm.mlp/add"}
}
"""


def test_a_kernel_reads_its_scope_once_joined():
    plain = sc.op_table([KERNEL])["jit_step"]
    assert plain["splash_mqa_fwd.1"] == ("custom-call", "")
    table = sc.op_table([sj.joined(KERNEL)])["jit_step"]
    assert table["splash_mqa_fwd.1"] == ("custom-call", "lm.attention")
    assert table["add.2"] == ("add", "lm.mlp")
    assert sj.joined(sj.joined(KERNEL)) == sj.joined(KERNEL)


def test_gaps_longest_first_with_their_module_run():
    ops = [("a", 0, 1_000_000, "fusion", "lm.mlp"),
           ("b", 1_050_000, 2_000_000, "fusion", "lm.mlp"),
           ("c", 2_500_000, 3_000_000, "custom-call", "lm.attention"),
           ("d", 3_200_000, 3_300_000, "add", "lm.loss")]
    t = sc.Scoped({"/device:TPU:0": ops},
                  {"/device:TPU:0": [("jit_step", 0, 3_000_000),
                                     ("jit_loss", 3_100_000, 3_400_000)]},
                  [])
    got = sj.gaps(t)
    assert [(g["after"], g["before"], g["ms"]) for g in got] == [
        ("b", "c", 0.5), ("c", "d", 0.2)]
    assert got[0]["in_module_run"] == ["jit_step"]
    assert got[1]["in_module_run"] == []
