"""C004/C005 — ``ModelConfig.cache_key()`` soundness.

The jit caches in the simulator, launch and serving layers are all
keyed on ``cache_key()``, so the key must partition configs exactly
like the traced programs they produce:

* **C004 (under-keying)** — two configs with EQUAL keys whose round /
  decode programs differ: the second config would silently reuse the
  first one's compiled program (the PR-4 stale-closure bug class, now
  proven absent by abstract interpretation instead of assumed).
* **C005 (over-keying)** — two configs with UNEQUAL keys whose
  programs are identical on BOTH canonical surfaces: every such field
  doubles compile time and cache footprint for nothing. Fields that
  are *identity metadata* (``arch_id``, ``source``) are allowlisted —
  they key checkpoints and result tables, not programs.

Program identity is the jaxpr text of two canonical program builders —
the training loss/grad surface (``loss_fn``) and the serving decode
step (``decode_step``) — traced with each variant's OWN abstract
params/cache trees, so dtype and structural fields propagate into the
comparison. Structurally entangled fields (``family``, ``mla``, ...)
cannot be varied standalone on a frozen config and are explicitly
skipped with reasons (reported in ``stats``), not silently dropped.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.contracts.base import contract_finding
from repro.analysis.findings import Finding

PATH = "src/repro/configs/base.py"
HINT_UNDER = ("cache_key() must distinguish every pair of configs that "
              "trace to different programs — add the drifting field to "
              "the frozen config (or stop reading it at trace time)")
HINT_OVER = ("field changes the key but not the traced programs: either "
             "allowlist it as identity metadata in "
             "repro.analysis.contracts.cache_keys.OVERKEY_OK or drop it "
             "from the key")

SDS = jax.ShapeDtypeStruct

#: identity-metadata fields: allowed to split the key without changing
#: the program (they key checkpoints, goldens and result tables)
OVERKEY_OK = frozenset({"arch_id", "source"})

#: fields that cannot be varied standalone on a frozen ModelConfig —
#: skipped with a reason so coverage stays honest
SKIP = {
    "family": "selects the whole block structure; varied via arch families",
    "attn_kind": "entangled with mla/family (gqa vs mla block)",
    "mla": "structural sub-config; covered by the deepseek arch family",
    "moe": "structural sub-config; covered by the moe arch families",
    "mamba": "structural sub-config; covered by the mamba arch family",
    "attn_period": "hybrid-only interleave; entangled with family",
    "attn_offset": "hybrid-only interleave; entangled with family",
    "frontend": "structural sub-config (audio/vision tower)",
    "n_frontend_tokens": "only traced when a frontend is present",
    "mrope": "rope variant entangled with mrope_sections",
    "mrope_sections": "only traced when mrope is set",
    "is_encdec": "selects the enc-dec program family",
    "n_enc_layers": "only traced when is_encdec",
    "kernel_backend": "resolution folded into the key; checked as the "
                      "auto-vs-resolved positive control instead",
}

# one-field variants probed against the base reduced llama proxy; every
# ModelConfig field must appear here, in SKIP, or in the control below
# (pinned by tests/test_contracts.py)
VARIANTS = (
    # n_heads grows (8) rather than shrinks: the reduced llama proxy is
    # MHA with 4 kv heads, and a standalone n_heads < n_kv_heads is not
    # a constructible config
    ("n_layers", 3), ("d_model", 128), ("n_heads", 8), ("n_kv_heads", 1),
    ("d_ff", 256), ("vocab", 256), ("head_dim", 32), ("qk_norm", True),
    ("qkv_bias", True), ("rope_theta", 100000.0),
    ("sliding_window", 8), ("norm_eps", 1e-5), ("tie_embeddings", True),
    ("nope", True), ("embedding_multiplier", 12.0),
    # 0.5, not 0.25: the proxy's default score scale is 1/sqrt(16)
    ("attention_multiplier", 0.5), ("residual_multiplier", 0.22),
    ("logits_scaling", 8.0),
    ("dtype", "float32"), ("arch_id", "renamed-proxy"),
    ("source", "contract-probe"),
)


def _base_cfg():
    from repro.configs import get_config, reduce_config
    from repro.configs.base import ReducedSpec

    return reduce_config(get_config("llama2-7b-proxy"),
                         ReducedSpec(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=128))


def _programs(cfg) -> Tuple[str, str]:
    """Jaxpr text of the two canonical surfaces, with the variant's own
    abstract model/cache trees (so dtype/structure fields propagate)."""
    from repro.models import transformer as T

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
    lora = jax.eval_shape(
        lambda k: T.init_lora(cfg, k, rank=4), key)
    batch = {"tokens": SDS((2, 16), jnp.int32),
             "labels": SDS((2, 16), jnp.int32)}
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 16))

    def train(p, lo, b):
        # window threads exactly like the launch layer does it
        # (cfg.effective_window -> loss_fn's explicit operandless kwarg),
        # so sliding_window participates in program identity
        return T.loss_fn(cfg, p, lo, b, window=cfg.sliding_window)

    def decode(p, lo, tok, ca):
        return T.decode_step(cfg, p, lo, tok, ca)

    train_text = str(jax.jit(train).trace(params, lora, batch).jaxpr)
    decode_text = str(jax.jit(decode).trace(
        params, lora, SDS((2, 1), jnp.int32), cache).jaxpr)
    return train_text, decode_text


def check_cache_keys() -> Tuple[List[Finding], Dict[str, int]]:
    import dataclasses

    base = _base_cfg()
    base_key = base.cache_key()
    base_progs = _programs(base)
    findings: List[Finding] = []
    n_pairs = 0

    def compare(surface, cfg, expect_named_field=None):
        nonlocal n_pairs
        n_pairs += 1
        try:
            progs = _programs(cfg)
        except Exception as e:
            findings.append(contract_finding(
                "C004", PATH, surface,
                f"abstract trace failed: {type(e).__name__}: {e}",
                HINT_UNDER))
            return
        key_eq = cfg.cache_key() == base_key
        prog_eq = progs == base_progs
        if key_eq and not prog_eq:
            which = [s for s, (a, b) in zip(("train", "decode"),
                                            zip(progs, base_progs))
                     if a != b]
            findings.append(contract_finding(
                "C004", PATH, surface,
                f"equal cache_key() but the {'/'.join(which)} "
                f"program(s) differ — a jit cache keyed on it would "
                f"reuse a stale program", HINT_UNDER))
        elif (not key_eq and prog_eq
              and expect_named_field not in OVERKEY_OK):
            findings.append(contract_finding(
                "C005", PATH, surface,
                f"cache_key() splits on {expect_named_field!r} but both "
                f"canonical programs are identical — the field compiles "
                f"duplicate programs", HINT_OVER))

    for field, value in VARIANTS:
        compare(f"cache-key:{field}={value}",
                dataclasses.replace(base, **{field: value}),
                expect_named_field=field)

    # positive control: auto resolves to a concrete backend on this
    # host; the resolved config MUST share both key and program
    resolved = dataclasses.replace(
        base, kernel_backend=base.cache_key().kernel_backend)
    compare("cache-key:auto-vs-resolved", resolved,
            expect_named_field="kernel_backend")
    if resolved.cache_key() != base_key:
        findings.append(contract_finding(
            "C004", PATH, "cache-key:auto-vs-resolved",
            "auto and its platform resolution must share one "
            "cache_key()", HINT_UNDER))

    covered = {f for f, _ in VARIANTS} | set(SKIP) | {"kernel_backend"}
    missing = {f.name for f in dataclasses.fields(type(base))} - covered
    for field in sorted(missing):
        findings.append(contract_finding(
            "C004", PATH, f"cache-key:uncovered:{field}",
            f"ModelConfig field {field!r} is neither probed by a "
            f"variant nor listed in SKIP — new trace-relevant fields "
            f"must join the soundness matrix", HINT_UNDER))

    stats = {"cache_key_pairs": n_pairs,
             "cache_key_skipped": len(SKIP)}
    return findings, stats
