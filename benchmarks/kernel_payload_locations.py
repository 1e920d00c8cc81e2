"""What a Pallas kernel's program carries into its compile-cache key.

    JAX_PLATFORMS=cpu python benchmarks/kernel_payload_locations.py [<checkout>] [--save <file>]

jax strips an operation's source locations from the persistent cache's
key, but a ``tpu_custom_call`` holds its Mosaic module as an opaque
payload, locations and all. This builds a small state-space PPO policy
on the fused lane in ``<checkout>`` (default: this one), takes the
``ssd_step`` kernel wherever the model would on a TPU, lowers the fused
program for the TPU platform from the CPU THROUGH THE REAL CALL PATH
(``Algorithm.train()`` down to ``ShardedFunction.__call__``), decodes
each kernel's payload and prints the files, lines and function names it
names. ``--save`` writes the decoded payloads, one after another, for a
``diff`` between two checkouts.

Found with it (PR 36): the payload names the TEN innermost frames of
the kernel's call (``jax_traceback_in_locations_limit``) by ABSOLUTE
file path, line and column. So the programs that hold a kernel get a
new cache key from a moved line in ``ops/ssd.py`` / ``ops/deltanet.py``,
``models/sequence_lm`` or at ``JaxPolicy._action_step_body``'s and
``model_forward``'s call of the model, AND from the checkout's own
directory: two checkouts of one tree never share those entries.
"""

from __future__ import annotations

import base64
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

LM = {
    "hidden_size": 128, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.1, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": 32, "mamba_d_head": 8, "mamba_d_state": 128,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "shared_intermediate_size": 48, "intermediate_size": 48,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 32,
    "tie_word_embeddings": True, "position_embedding_type": "nope",
}
CONFIG = {
    "env": "TokenStreamJax-v0", "gamma": 1.0, "lambda": 0.95,
    "clip_param": 0.2, "lr": 1e-4, "grad_clip": 1.0, "kl_coeff": 0.0,
    "entropy_coeff": 0.0, "vf_loss_coeff": 1.0, "vf_clip_param": 10.0,
    "num_sgd_iter": 1, "env_backend": "jax", "num_workers": 0,
    "num_envs_per_worker": 8, "rollout_fragment_length": 16,
    "train_batch_size": 128, "sgd_minibatch_size": 128, "superstep": 1,
    "model": {"use_sequence_lm": True, "max_seq_len": 16,
              "sequence_lm": LM, "dtype": "float32"},
    "env_config": {"vocab_size": 64, "episode_length": 32, "phase_stride": 4},
}


class _Lowered(Exception):
    pass


def fused_program_text(root: str) -> str:
    """The fused program's module as text, lowered for the TPU platform
    where ``Algorithm.train()`` would have dispatched it."""
    sys.path.insert(0, root)
    os.chdir(root)
    from ray_tpu.algorithms.registry import get_algorithm_class
    from ray_tpu.ops import ssd
    from ray_tpu.sharding import compile as compile_lib

    ssd._kernel_applies = lambda state, groups=1: state.ndim == 5  # as on a TPU

    class Dispatch:
        def __init__(self, jitted, label):
            self.jitted, self.label = jitted, label

        def __getattr__(self, name):
            return getattr(self.jitted, name)

        def __call__(self, *args, **kwargs):
            if not self.label.startswith("rollout_superstep"):
                return self.jitted(*args, **kwargs)
            lowered = self.jitted.trace(*args, **kwargs).lower(
                lowering_platforms=("tpu",)
            )
            raise _Lowered(lowered.as_text(debug_info=True))

    init = compile_lib.ShardedFunction.__init__

    def init_and_wrap(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self._jitted = Dispatch(self._jitted, self.label)

    compile_lib.ShardedFunction.__init__ = init_and_wrap
    try:
        get_algorithm_class("PPO")(config=dict(CONFIG)).train()
    except _Lowered as lowered:
        return str(lowered)
    raise SystemExit("the fused program was never dispatched")


def payloads(text: str):
    """Each ``tpu_custom_call``'s Mosaic module, decoded to text with
    its locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    out = []
    for found in re.finditer(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(found.group(1)))
            out.append(module.operation.get_asm(enable_debug_info=True))
    return out


def main(argv) -> int:
    save = None
    if "--save" in argv:
        at = argv.index("--save")
        save = os.path.abspath(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    root = os.path.abspath(
        argv[0] if argv else os.path.join(os.path.dirname(__file__), "..")
    )
    decoded = payloads(fused_program_text(root))
    print(f"{len(decoded)} kernel calls, {len(set(decoded))} distinct payloads")
    for asm in sorted(set(decoded)):
        files = re.findall(r'loc\("(/[^"]+)":(\d+):(\d+)', asm)
        # a frame is a named location that stands in a callsite chain
        named = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"\(#loc\d+\)\)', asm))
        in_chain = set(re.findall(r'#loc\d+', " ".join(
            re.findall(r'callsite\(([^)]*)\)', asm))))
        frames = []
        for loc, name in named.items():  # innermost first
            if loc in in_chain and name not in frames:
                frames.append(name)
        print("  files:", sorted({f for f, _, _ in files}))
        print("  lines named:", len({(f, line) for f, line, _ in files}))
        print("  frames:", " <- ".join(frames))
    if save:
        with open(save, "w") as f:
            f.write("\n".join(decoded))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
