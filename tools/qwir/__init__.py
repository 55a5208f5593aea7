"""qwir — jaxpr-level static auditing of the lowered leaf hot path.

qwlint (tools/qwlint) checks the *source*; qwmc (tools/qwmc) checks the
*protocols*; qwir checks the *artifact*: the lowered JAX programs the TPU
actually runs. It abstract-evals (never executes, never compiles) a
representative plan corpus — `search/plan.py` lowerings enumerated across
format versions, padding buckets, threshold/mask_override/count_override
variants, single-split / multi-query / mesh-batch / mask-fill paths —
and runs five rules over the resulting jaxprs:

  R1 compile-cache-closure  the set of (cache key, jaxpr digest) pairs
                            over the corpus is finite and exactly matches
                            the checked-in manifest (pinned program count)
  R2 f64-promotion-leak     no f64 sorts / doc-scale f64 promotions in
                            leaf kernels outside certified sites
  R3 host-round-trip        no callback/transfer primitives inside any
                            audited program
  R4 collective-soundness   every collective names a live mesh axis
  R5 hbm-ceiling            buffer-liveness peak bytes within the per-doc
                            budget and the admission quantum

Entry point: `python -m tools.qwir audit` (see __main__.py).
"""

from .audit import run_audit  # noqa: F401
from .rules import Finding  # noqa: F401
