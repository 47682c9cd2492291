"""What each engine step did, rebuilt from what the program exposes.

The engine records one ``ScheduleEvent`` per step (plan, decode lanes,
prefill tokens) and every request's ``admit_step``; the harness stamps every
emitted token with the engine's step count. From those this module rebuilds
each step's work: the real context of every lane it decoded, and which
prompt chunk (and at what offset) or which whole prompts it prefilled.

A token with index ``j >= 1`` was decoded by the step whose count it was
stamped with, from a lane holding ``prompt + j`` tokens once the fed token
was appended. Index 0 is a prefill's first token. Admission streams run one
at a time in ``admit_step`` order; a batch admission prefills whole prompts
at the step its requests were admitted.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from . import counts


@dataclass
class Step:
    index: int                  # event index (the step that made count index+1)
    kind: str                   # decode | fused | split | chunk | prefill | idle
    ctxs: list = field(default_factory=list)   # decoded lanes' contexts
    chunk: int = 0              # admission-chunk tokens
    chunk_off: int = 0          # cached prompt tokens before the chunk
    chunk_last: bool = False    # the chunk ends its prompt
    prefill_lens: list = field(default_factory=list)  # whole prompts prefilled

    def work(self, dims) -> "counts.Work":
        w = counts.decode_step(dims, self.ctxs)
        if self.chunk:
            # a fused or split step's decode half already reads the LM head
            rows = 1 if self.chunk_last else 0
            if rows and self.ctxs:
                w = w + counts.Work(flops=2.0 * dims.d * dims.vocab)
                rows = 0
            w = w + counts.chunk_prefill(dims, self.chunk, self.chunk_off, rows)
        if self.prefill_lens:
            w = w + counts.full_prefill(dims, self.prefill_lens)
        return w


def rebuild(events, stamps, admit_steps, prompt_lens, reused) -> list[Step]:
    """``stamps[r]``: (step count, token index) of request ``r``'s tokens;
    ``admit_steps[r]``: its admit_step (None if never admitted);
    ``reused[r]``: prompt tokens it took from the prefix store."""
    steps = [Step(e, "idle") for e in range(len(events))]
    for r, toks in enumerate(stamps):
        for count, j in toks:
            if j >= 1 and 0 < count <= len(events):
                steps[count - 1].ctxs.append(prompt_lens[r] + j)

    by_admit: dict[int, list[int]] = {}
    for r, a in enumerate(admit_steps):
        if a is not None:
            by_admit.setdefault(a, []).append(r)
    stream = None  # [request, consumed]
    for e, ev in enumerate(events):
        st = steps[e]
        p = ev.prefill_tokens
        if p > 0:
            group = by_admit.get(e, [])
            if stream is None and group and \
                    sum(prompt_lens[r] - reused[r] for r in group) == p \
                    and all(reused[r] == 0 for r in group):
                # whole prompts at their admission step (a one-chunk stream
                # does the same work)
                st.prefill_lens = [prompt_lens[r] for r in group]
            else:
                if stream is None:
                    if len(group) != 1:
                        raise ValueError(
                            f"step {e}: {p} prefill tokens but "
                            f"{len(group)} requests admitted there")
                    stream = [group[0], reused[group[0]]]
                r, off = stream
                st.chunk, st.chunk_off = p, off
                stream[1] += p
                if stream[1] >= prompt_lens[r]:
                    st.chunk_last = True
                    stream = None
        if st.chunk or st.prefill_lens:
            if ev.plan.fused:
                st.kind = "fused"
            elif ev.plan.decode:
                st.kind = "split"
            else:
                st.kind = "prefill" if st.prefill_lens else "chunk"
        elif ev.plan.decode:
            st.kind = "decode"
    return steps
