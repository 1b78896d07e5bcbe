"""Model Deployment Card (MDC): canonical, serializable model metadata.

Reference: `ModelDeploymentCard` (lib/llm/src/model_card/model.rs:94-230) and
its builders from an HF-style local repo (model_card/create.rs:41-185). The
card is what travels through discovery so frontends/routers can preprocess for
a model they never loaded: tokenizer artifact, context length, EOS ids, chat
template, and a content checksum (`mdcsum`) used to verify that two processes
agree on preprocessing.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Dict, List, Optional

from .tokenizer import HuggingFaceTokenizer, load_tokenizer, read_special_token_ids


@dataclasses.dataclass
class ModelInfo:
    """Reference model_card `ModelInfo`: generation-relevant config."""

    model_type: str = "llama"
    context_length: int = 4096
    vocab_size: int = 0
    eos_token_ids: List[int] = dataclasses.field(default_factory=list)
    bos_token_id: Optional[int] = None


@dataclasses.dataclass
class PromptFormatArtifact:
    """Chat-template artifact (reference model_card `PromptFormatterArtifact`,
    incl. the `.jinja`-file quirk handled in preprocessor/prompt/template)."""

    chat_template: Optional[str] = None
    add_generation_prompt: bool = True


@dataclasses.dataclass
class ModelDeploymentCard:
    display_name: str
    service_name: str
    model_path: Optional[str] = None
    tokenizer_file: Optional[str] = None
    model_info: ModelInfo = dataclasses.field(default_factory=ModelInfo)
    prompt_format: PromptFormatArtifact = dataclasses.field(default_factory=PromptFormatArtifact)
    model_type: str = "chat"  # "chat" | "completion" (reference model_type.rs:36)
    revision: int = 0

    _tokenizer: Optional[HuggingFaceTokenizer] = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- serialization -----------------------------------------------------
    def to_json_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.pop("_tokenizer", None)
        return d

    @classmethod
    def from_json_dict(cls, d: Dict[str, Any]) -> "ModelDeploymentCard":
        d = dict(d)
        d.pop("_tokenizer", None)
        info = d.pop("model_info", {}) or {}
        fmt = d.pop("prompt_format", {}) or {}
        return cls(model_info=ModelInfo(**info),
                   prompt_format=PromptFormatArtifact(**fmt), **d)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json_dict(), f, indent=2)

    @classmethod
    def load(cls, path: str) -> "ModelDeploymentCard":
        with open(path) as f:
            return cls.from_json_dict(json.load(f))

    def mdcsum(self) -> str:
        """Content checksum (reference `mdcsum`, model_card/model.rs)."""
        blob = json.dumps(self.to_json_dict(), sort_keys=True).encode()
        return hashlib.blake2s(blob, digest_size=16).hexdigest()

    # -- tokenizer ---------------------------------------------------------
    def tokenizer(self) -> HuggingFaceTokenizer:
        if self._tokenizer is None:
            src = self.tokenizer_file or self.model_path
            if src is None:
                raise RuntimeError(f"MDC {self.display_name} has no tokenizer artifact")
            self._tokenizer = load_tokenizer(src)
        return self._tokenizer

    # -- builders ----------------------------------------------------------
    @classmethod
    def from_local_path(cls, model_dir: str,
                        display_name: Optional[str] = None) -> "ModelDeploymentCard":
        """Build from an HF-style directory (reference model_card/create.rs:41-185):
        reads tokenizer.json, config.json, generation_config.json and
        tokenizer_config.json (chat_template, incl. separate *.jinja files)."""
        name = display_name or os.path.basename(os.path.normpath(model_dir))
        card = cls(display_name=name, service_name=name, model_path=model_dir)
        tok_file = os.path.join(model_dir, "tokenizer.json")
        if os.path.exists(tok_file):
            card.tokenizer_file = tok_file
        tk = card.tokenizer()
        specials = read_special_token_ids(model_dir, tk)
        cfg: Dict[str, Any] = {}
        cfg_path = os.path.join(model_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                cfg = json.load(f)
        card.model_info = ModelInfo(
            model_type=cfg.get("model_type", "llama"),
            # kimi_linear states its limit as model_max_length alone
            context_length=int(cfg.get("max_position_embeddings")
                               or cfg.get("model_max_length") or 4096),
            vocab_size=int(cfg.get("vocab_size", tk.vocab_size)),
            eos_token_ids=specials["eos_token_ids"],
            bos_token_id=specials["bos_token_id"],
        )
        card.prompt_format = _load_chat_template(model_dir)
        return card


def _load_chat_template(model_dir: str) -> PromptFormatArtifact:
    """chat_template from tokenizer_config.json; handles the list-valued form
    and standalone chat_template.jinja files (reference
    preprocessor/prompt/template/tokcfg.rs quirks)."""
    art = PromptFormatArtifact()
    cfg_path = os.path.join(model_dir, "tokenizer_config.json")
    template: Any = None
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            template = json.load(f).get("chat_template")
    if template is None:
        for name in ("chat_template.jinja", "chat_template.json"):
            p = os.path.join(model_dir, name)
            if os.path.exists(p):
                with open(p) as f:
                    raw = f.read()
                if name.endswith(".json"):
                    try:
                        template = json.loads(raw).get("chat_template")
                    except json.JSONDecodeError:
                        template = None
                else:
                    template = raw
                break
    if isinstance(template, list):
        # list of {name, template} — prefer "default"
        by_name = {t.get("name"): t.get("template") for t in template
                   if isinstance(t, dict)}
        template = by_name.get("default") or next(iter(by_name.values()), None)
    if isinstance(template, str):
        art.chat_template = template
    return art
