"""Roofline share of a decode step's routed-expert matmuls: touched experts x
3 x hidden x expert width x 2 B (and the FLOPs of the token-expert pairs)
over the traced time of the expert loops of the decode program: the ``while``
operations that carry ``f32[slots, hidden]`` (one a resident expert a sparse
layer; the trace gives a loop the time of everything it runs, and a loop no
token chose takes none) and the prefetches of an expert-shaped matrix that
the compiled program starts ahead of them (``copy-done`` of ``[hidden,
expert_width]`` or its transpose; the shared expert's have that shape too, so
the share reads low rather than high). The steps in the trace are its
``paged_mla_decode_attn`` calls over the layers. None where the trace holds
neither."""

from benchmark import work, work_kimi_k2


def read(obs):
    tr, peaks, fam = obs.get("trace"), obs.get("peaks"), obs.get("family")
    if not tr or not peaks or not fam:
        return None
    m = fam["shapes"]
    mean = work_kimi_k2.per_step(m, fam.get("traced_counters"))
    loop = "f32[%d,%d]" % (m["slots"], m["hidden"])
    matrix = ("[%d,%d]" % (m["hidden"], m["expert_width"]),
              "[%d,%d]" % (m["expert_width"], m["hidden"]))
    spent = sum(sec for name, sec in tr["device_ops"]
                if (name.startswith("while:") and loop in name)
                or (name.startswith("copy-done:") and any(s in name for s in matrix)))
    steps = sum(agg["calls"] for name, agg in tr["mosaic_calls"].items()
                if "paged_mla_decode_attn" in name) / m["layers"]
    if not spent or not steps or mean is None:
        return None
    flops, nbytes = work_kimi_k2.expert_matmul_work(
        m, touched=mean["touched"], assignments=mean["assignments"])
    return 100.0 * steps * work.least_seconds(flops, nbytes, peaks) / spent
