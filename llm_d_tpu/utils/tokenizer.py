"""Tokenizer facade: HuggingFace when available, byte-level fallback.

The EPP needs a tokenizer too (the reference ships a HF tokenizer inside the
scheduler for precise prefix hashing; reference: SURVEY.md §2 "HF tokenizer in
EPP"), so this module must be importable without JAX or model weights.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence


class ByteTokenizer:
    """Deterministic, dependency-free tokenizer: UTF-8 bytes + specials.

    Used by tests, the simulator, and any deployment without a HF tokenizer
    artifact. Vocabulary: 256 byte tokens, then BOS/EOS/PAD.
    """

    def __init__(self) -> None:
        self.bos_token_id = 256
        self.eos_token_id = 257
        self.pad_token_id = 258
        self.vocab_size = 259

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_token_id] + ids) if add_bos else ids

    def decode(self, ids: Sequence[int]) -> str:
        data = bytes(i for i in ids if i < 256)
        return data.decode("utf-8", errors="replace")

    def stream_decoder(self) -> Callable[[Sequence[int]], str]:
        """``decode`` for ONE id list that only grows (a streamed answer,
        called a frame after a frame with the whole list): the full
        decode's text, letter for letter."""
        return _ByteStream()


class _ByteStream:
    """``ByteTokenizer.decode`` of an id list that only grows: each call
    filters the ids added since the last one and decodes the bytes kept so
    far, so a frame of a long answer costs its new tokens, not the answer."""

    def __init__(self) -> None:
        self._data = bytearray()
        self._seen = 0

    def __call__(self, ids: Sequence[int]) -> str:
        n = len(ids)            # another thread may append while this runs
        self._data.extend(i for i in ids[self._seen:n] if i < 256)
        self._seen = n
        return self._data.decode("utf-8", errors="replace")


class HFTokenizer:
    """Thin wrapper over ``transformers.AutoTokenizer``."""

    def __init__(self, name_or_path: str) -> None:
        from transformers import AutoTokenizer  # lazy: heavy import

        self._tok = AutoTokenizer.from_pretrained(name_or_path)
        self.bos_token_id = self._tok.bos_token_id
        self.eos_token_id = self._tok.eos_token_id
        self.pad_token_id = self._tok.pad_token_id or self._tok.eos_token_id
        self.vocab_size = len(self._tok)

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        return self._tok.encode(text, add_special_tokens=add_bos)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)

    def stream_decoder(self) -> Callable[[Sequence[int]], str]:
        # A merge-based vocabulary's text is not the sum of its tokens'
        # texts: the whole list is decoded every time.
        return self.decode


def get_tokenizer(name_or_path: Optional[str]):
    """``None``/"byte" -> ByteTokenizer, else HF."""
    if name_or_path in (None, "", "byte"):
        return ByteTokenizer()
    return HFTokenizer(name_or_path)
