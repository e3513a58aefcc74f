from .pipeline import DataPipeline, FileTokenSource, SyntheticTokenSource

__all__ = ["DataPipeline", "FileTokenSource", "SyntheticTokenSource"]
