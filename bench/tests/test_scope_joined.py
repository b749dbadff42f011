"""A Mosaic kernel's multi-line attribute block joined onto its
instruction's line by ``bench.scopes.op_table``, so that its scope is
read; and ``bench/scope_report.py --gaps``."""
from bench import scope_report as sr, scopes as sc

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
    # one line an instruction: the kernel's op_name sits three lines below
    line = next(x for x in KERNEL.splitlines() if "splash_mqa_fwd.1 =" in x)
    assert "op_name" not in line
    table = sc.op_table([KERNEL])["jit_step"]
    assert table["splash_mqa_fwd.1"] == ("custom-call", "lm.attention")
    assert table["add.2"] == ("add", "lm.mlp")
    assert sc.op_table([sc.joined(KERNEL)]) == sc.op_table([KERNEL])
    assert sc.joined(sc.joined(KERNEL)) == sc.joined(KERNEL)


def test_gaps_longest_first_with_their_module_run():
    ops = [("a", 0, 1_000_000, "fusion", "lm.mlp"),
           ("b", 1_050_000, 2_000_000, "fusion", "lm.mlp"),
           ("c", 2_500_000, 3_000_000, "custom-call", "lm.attention"),
           ("d", 3_200_000, 3_300_000, "add", "lm.loss")]
    t = sc.Scoped({"/device:TPU:0": ops},
                  {"/device:TPU:0": [("jit_step", 0, 3_000_000),
                                     ("jit_loss", 3_100_000, 3_400_000)]},
                  [])
    got = sr.gaps(t)
    assert [(g["after"], g["before"], g["ms"]) for g in got] == [
        ("b", "c", 0.5), ("c", "d", 0.2)]
    assert got[0]["in_module_run"] == ["jit_step"]
    assert got[1]["in_module_run"] == []
