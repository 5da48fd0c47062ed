from .dedup import minhash_dedup, document_sketches

__all__ = ["minhash_dedup", "document_sketches"]
