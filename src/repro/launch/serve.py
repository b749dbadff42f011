"""Batched serving driver: prefill → decode loop with a request queue.

Serving path of the framework: requests arrive with prompts, get batched
to the configured batch size, prefilled once (cache written decode-ready),
then stepped token-by-token. Params are cast to bf16. The same code path
runs the CPU smoke demo and a pod deployment.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --requests 4 --gen-tokens 8
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_arch, get_smoke
from repro.config.cli import build_parser
from repro.launch.mesh import (make_production_mesh, make_test_mesh,
                               production_mesh_config, test_mesh_config)
from repro.models.registry import build_model
from repro.sharding import rules_for, use_rules


class ServeEngine:
    def __init__(self, model_cfg, mesh, mesh_cfg, max_len: int = 128,
                 dtype=jnp.bfloat16):
        self.cfg = model_cfg
        self.mesh = mesh
        self.rules = rules_for(mesh_cfg, mesh)
        self.model = build_model(model_cfg)
        self.max_len = max_len
        self.dtype = dtype
        with jax.set_mesh(mesh), use_rules(self.rules):
            params = self.model.init(jax.random.key(0))
            self.params = jax.tree.map(
                lambda p: p.astype(dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

        def prefill(params, batch):
            with use_rules(self.rules):
                return self.model.prefill(params, batch)

        def decode(params, batch):
            with use_rules(self.rules):
                return self.model.decode_step(params, batch)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    def generate(self, prompts: np.ndarray, gen_tokens: int,
                 extras=None) -> np.ndarray:
        """prompts: (B, S_prompt) int32 → (B, gen_tokens) int32 greedy."""
        b, s_prompt = prompts.shape
        batch = {"tokens": jnp.asarray(prompts)}
        if extras:
            batch.update(extras)
        with jax.set_mesh(self.mesh):
            logits, cache = self._prefill(self.params, batch)
            # grow the prefill cache out to max_len for decode-in-place
            cache = self._grow_cache(cache, b)
            out = []
            index = s_prompt
            token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            for _ in range(gen_tokens):
                out.append(np.asarray(token)[:, 0])
                logits, cache = self._decode(
                    self.params, {"token": token, "cache": cache,
                                  "index": jnp.int32(index)})
                token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
                index += 1
        return np.stack(out, axis=1)

    def _grow_cache(self, cache, batch_size: int):
        """Pad seq-dim cache buffers from prompt length to max_len."""
        full = self.model.init_cache(batch_size, self.max_len,
                                     dtype=self.dtype)

        def merge(dst, src):
            if dst.shape == src.shape:
                return src.astype(dst.dtype)
            pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
            return jnp.pad(src.astype(dst.dtype), pad)

        return jax.tree.map(merge, full, cache)


def main() -> None:
    p = build_parser("batched serving driver")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--multi-pod", action="store_true",
                   help="use the 2x16x16 mesh")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen-tokens", type=int, default=8)
    args = p.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    if args.smoke:
        n_dev = len(jax.devices())
        mesh, mesh_cfg = make_test_mesh((n_dev, 1)), test_mesh_config((n_dev, 1))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        mesh_cfg = production_mesh_config(multi_pod=args.multi_pod)

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size,
                           size=(args.requests, args.prompt_len),
                           dtype=np.int32)
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jnp.zeros(
            (args.requests, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        extras["frames"] = jnp.zeros(
            (args.requests, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)

    engine = ServeEngine(cfg, mesh, mesh_cfg,
                         max_len=args.prompt_len + args.gen_tokens + 1)
    t0 = time.time()
    tokens = engine.generate(prompts, args.gen_tokens, extras=extras)
    dt = time.time() - t0
    print(json.dumps({
        "arch": cfg.name,
        "requests": args.requests,
        "generated": tokens.shape[1],
        "tokens_per_s": round(tokens.size / dt, 1),
        "sample": tokens[0].tolist(),
    }))


if __name__ == "__main__":
    main()
