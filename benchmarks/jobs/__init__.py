"""`jobs/<model_type>.py`: a published `config.json`'s keys worded as the
program's `model` group (`model_group(config) -> dict`), found by the
configuration's `model_type` (`drivers/resident_sequences_any_model.py`)."""
