"""Heterogeneous-swarm message interop: the reference protobuf wire.

The reference swarm's CUDA/SGLang, vLLM and MLX nodes exchange
``ForwardRequest`` / ``AbortRequest`` protobuf messages with
safetensors-serialized hidden states
(``src/parallax/p2p/proto/forward.proto:1-57`` +
``src/parallax/p2p/message_util.py:18-236``). This module speaks that
message format bit-for-bit — encode this framework's
:class:`IntermediateRequest` into reference-compatible bytes and decode
reference-encoded bytes back — so a reference-protocol stage can exchange
activations with a TPU stage through any byte transport.

Scope (also documented in PARITY.md): interop is implemented at the
MESSAGE layer. The reference's byte TRANSPORT is Lattica (libp2p streams
+ DHT + DCUtR); this framework's is length-prefixed TCP. A mixed swarm
therefore needs a thin bridge process that moves opaque protobuf payloads
between the two transports — the semantic translation lives here, and
``WorkerNode`` accepts raw protobuf payloads on its ``rpc_pp_forward`` /
``rpc_abort`` handlers directly.

Tensor payloads: the reference serializes via safetensors (torch on CUDA,
mlx elsewhere) under the key ``"tensor"``. We use safetensors.torch (CPU)
for both directions, which round-trips every dtype the reference sends
(including bf16, which numpy lacks); bf16 arrays surface as float32 numpy
with the original dtype recorded on the wire only.
"""

from __future__ import annotations

import os
import subprocess
from typing import Iterable

import numpy as np

from parallax_tpu.runtime.request import IntermediateRequest, SamplingParams
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


def _load_pb2():
    """Import the generated schema module, generating it from
    ``interop.proto`` on first use (same on-demand pattern as the native
    C++ cache build). The generated file is never committed — the .proto
    IS the interop contract; protoc's output is an artifact."""
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.path.join(here, "interop_pb2.py")
    src = os.path.join(here, "interop.proto")
    if not os.path.exists(out) or (
        os.path.getmtime(out) < os.path.getmtime(src)
    ):
        tmp_dir = f"{out}.{os.getpid()}.d"
        os.makedirs(tmp_dir, exist_ok=True)
        try:
            subprocess.run(
                ["protoc", f"-I{here}", f"--python_out={tmp_dir}", src],
                check=True, capture_output=True, timeout=60,
            )
            os.replace(os.path.join(tmp_dir, "interop_pb2.py"), out)
        except (OSError, subprocess.SubprocessError) as e:
            raise ImportError(
                "interop needs the generated protobuf module; protoc "
                f"failed or is unavailable: {e}. Run: "
                f"protoc -I {here} --python_out={here} {src}"
            ) from e
        finally:
            try:
                os.rmdir(tmp_dir)
            except OSError:
                pass
    from parallax_tpu.p2p import interop_pb2

    return interop_pb2


pb = _load_pb2()


# -- tensors ----------------------------------------------------------------


def tensor_to_safetensors(arr: np.ndarray) -> bytes:
    """Reference ``tensor_to_bytes``: safetensors bytes under "tensor"."""
    import torch
    from safetensors.torch import save

    t = torch.from_numpy(np.ascontiguousarray(arr))
    return save({"tensor": t})


def tensor_from_safetensors(data: bytes) -> np.ndarray:
    """Reference ``bytes_to_tensor``; bf16 upcasts to f32 for numpy."""
    import torch
    from safetensors.torch import load

    t = load(bytes(data))["tensor"]
    if t.dtype == torch.bfloat16:
        t = t.to(torch.float32)
    return t.numpy()


# -- sampling params --------------------------------------------------------


def sampling_to_proto(sp: dict | SamplingParams) -> pb.SamplingParams:
    if isinstance(sp, SamplingParams):
        sp = sp.to_dict()
    sp = sp or {}
    out = pb.SamplingParams()
    out.max_new_tokens = int(sp.get("max_new_tokens", 128))
    out.min_new_tokens = int(sp.get("min_new_tokens", 0))
    out.temperature = float(sp.get("temperature", 1.0))
    out.top_p = float(sp.get("top_p", 1.0))
    out.min_p = float(sp.get("min_p", 0.0))
    out.top_k = int(sp.get("top_k", -1))
    out.stop_token_ids.extend(int(t) for t in sp.get("stop_token_ids") or ())
    out.ignore_eos = bool(sp.get("ignore_eos", False))
    out.stop_strs.extend(sp.get("stop_strings") or ())
    out.repetition_penalty = float(sp.get("repetition_penalty", 1.0))
    out.presence_penalty = float(sp.get("presence_penalty", 0.0))
    out.frequency_penalty = float(sp.get("frequency_penalty", 0.0))
    if sp.get("json_schema"):
        out.json_schema = sp["json_schema"]
    return out


def sampling_from_proto(p: pb.SamplingParams) -> dict:
    """To this framework's wire dict (``SamplingParams.from_dict`` form).
    Reference-only field ``min_new_tokens`` is preserved; fields the
    reference wire cannot carry (seed, logit_bias, logprobs) default."""
    return dict(
        max_new_tokens=p.max_new_tokens or 128,
        min_new_tokens=p.min_new_tokens,
        # return_probs lives on Req in the schema; the caller overlays it
        # (forward_bytes_to_ireqs) since this helper only sees
        # pb.SamplingParams.
        temperature=p.temperature,
        top_p=p.top_p if p.top_p > 0 else 1.0,
        min_p=p.min_p,
        top_k=p.top_k if p.top_k != 0 else -1,
        stop_token_ids=list(p.stop_token_ids),
        ignore_eos=p.ignore_eos,
        stop_strings=list(p.stop_strs),
        repetition_penalty=p.repetition_penalty or 1.0,
        presence_penalty=p.presence_penalty,
        frequency_penalty=p.frequency_penalty,
        json_schema=p.json_schema or None,
    )


# -- ForwardRequest ---------------------------------------------------------


def ireqs_to_forward_bytes(
    ireqs: list[IntermediateRequest],
    full_input_ids: dict[str, list[int]] | None = None,
) -> bytes:
    """Encode a batch of same-phase IntermediateRequests as a
    reference-compatible ``ForwardRequest``.

    Reference semantics (message_util.request_to_proto): ``input_ids``
    carries the PROMPT ids, ``output_length`` the generated count, so
    ``current_position = len(input_ids) + output_length`` is the total
    context. This framework's packets carry only the new tokens, so the
    caller provides each request's prompt via ``full_input_ids``
    (available on the head); without it the packet's own token ids stand
    in and output_length compensates to keep current_position exact.
    """
    msg = pb.ForwardRequest()

    def _is_prefill(i: IntermediateRequest) -> bool:
        return not i.abort and (
            i.num_new_tokens > 1 or i.context_len == i.num_new_tokens
        )

    kinds = {_is_prefill(i) for i in ireqs}
    msg.forward_mode = (
        pb.ForwardMode.MIXED if len(kinds) > 1
        else pb.ForwardMode.EXTEND if True in kinds
        else pb.ForwardMode.DECODE
    )
    for ireq in ireqs:
        r = msg.reqs.add()
        r.rid = ireq.request_id
        ids = (full_input_ids or {}).get(ireq.request_id)
        if ids is None:
            ids = list(ireq.cached_prefix_ids or []) + list(
                ireq.token_ids or []
            )
        r.input_ids.extend(int(t) for t in ids)
        r.output_length = ireq.context_len - len(ids)
        r.routing_table.extend(ireq.routing_table or [])
        r.sampling_params.CopyFrom(sampling_to_proto(ireq.sampling_params))
        r.lora_path = ireq.lora_id or ""
        if ireq.hidden_states is not None:
            r.hidden_states = tensor_to_safetensors(
                np.asarray(ireq.hidden_states)
            )
        if ireq.next_token_id is not None:
            r.next_token_id = int(ireq.next_token_id)
        elif not _is_prefill(ireq) and ireq.token_ids:
            # Decode forward packet: the reference wire carries the fed
            # token in next_token_id (input_ids stays the prompt); this
            # framework carries it in token_ids. Dropping it would make
            # the receiver decode token 0 — wrong penalties, wrong
            # embedding on a reference peer.
            r.next_token_id = int(ireq.token_ids[-1])
        if ireq.token_logprob is not None:
            r.token_prob = float(ireq.token_logprob)
        sp = ireq.sampling_params or {}
        r.return_probs = bool(
            (sp.get("logprobs") if isinstance(sp, dict) else sp.logprobs)
            or ireq.token_logprob is not None
        )
    return msg.SerializeToString()


def forward_bytes_to_ireqs(data: bytes) -> list[IntermediateRequest]:
    """Decode a reference-encoded ``ForwardRequest`` into this
    framework's IntermediateRequests (reference proto_to_request
    semantics: current_position = len(input_ids) + output_length; a
    request without hidden states is a finished/ring-closure packet)."""
    msg = pb.ForwardRequest()
    msg.ParseFromString(bytes(data))
    out: list[IntermediateRequest] = []
    for r in msg.reqs:
        hidden = (
            tensor_from_safetensors(r.hidden_states)
            if r.hidden_states else None
        )
        if hidden is not None and hidden.ndim == 1:
            hidden = hidden[None, :]
        current_position = len(r.input_ids) + r.output_length
        logprob = r.token_prob if r.HasField("token_prob") else None
        # Per-row phase: MIXED batches carry both kinds, so the batch
        # mode alone cannot be trusted. A decode row carries exactly one
        # hidden row AND has generated tokens (output_length > 0; a
        # multi-row packet is always a prefill hop, whatever its
        # output_length says — fallback chunk encodings shift it).
        decode = (
            msg.forward_mode == pb.ForwardMode.DECODE
            or (msg.forward_mode == pb.ForwardMode.MIXED
                and r.output_length > 0
                and hidden is not None and hidden.shape[0] == 1)
        )
        if hidden is None:
            # Reference semantics: no hidden states = a finished /
            # ring-closure packet; next_token_id is the sampled token the
            # head commits (this framework's commit-packet form).
            out.append(IntermediateRequest(
                request_id=r.rid,
                routing_table=list(r.routing_table),
                context_len=current_position,
                num_new_tokens=0,
                next_token_id=r.next_token_id,
                token_logprob=logprob,
                sampling_params=dict(
                sampling_from_proto(r.sampling_params),
                logprobs=bool(r.return_probs),
            ),
                lora_id=r.lora_path or None,
            ))
            continue
        n_new = int(hidden.shape[0])
        if decode:
            # DECODE: input_ids stays the prompt; the fed token is
            # next_token_id (the latest sampled token).
            tail = [int(r.next_token_id)]
        else:
            # EXTEND: the hop covers the tail of the context. Reference
            # encoders position input_ids absolutely (prompt so far); our
            # fallback encoding may pack only the chunk's own tokens, in
            # which case the whole payload IS the tail.
            ids = list(r.input_ids)
            if len(ids) >= current_position:
                tail = ids[current_position - n_new : current_position] or None
            elif len(ids) >= n_new:
                tail = ids[-n_new:]
            else:
                tail = None
        out.append(IntermediateRequest(
            request_id=r.rid,
            routing_table=list(r.routing_table),
            context_len=current_position,
            num_new_tokens=n_new,
            token_ids=tail,
            hidden_states=hidden,
            token_logprob=logprob,
            sampling_params=dict(
                sampling_from_proto(r.sampling_params),
                logprobs=bool(r.return_probs),
            ),
            is_last_chunk=True,
            lora_id=r.lora_path or None,
        ))
    return out


# -- AbortRequest -----------------------------------------------------------


def rids_to_abort_bytes(rids: Iterable[str]) -> bytes:
    msg = pb.AbortRequest()
    for rid in rids:
        msg.reqs.add().rid = rid
    return msg.SerializeToString()


def abort_bytes_to_rids(data: bytes) -> list[str]:
    msg = pb.AbortRequest()
    msg.ParseFromString(bytes(data))
    return [r.rid for r in msg.reqs]
