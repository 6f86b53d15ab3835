"""Plain float32 reference of the Jumbo-ViT encoder, the MAE decoder, the
pretraining loss and AdamW. Imports nothing from the program under test."""
